"""Pallas TPU kernels with tunable BlockSpec tilings.

Four benchmark-hub kernels (the paper's applications: dedispersion,
convolution, hotspot, GEMM) plus the framework's own hot spots (flash
attention, Mamba2 SSD). Each module provides: the ``pl.pallas_call`` kernel,
a jit'd wrapper, a pure-jnp oracle (``*_ref``), a tunable ``space()``, an
analytic ``workload()`` for the cost model, and a recording contract
(``SMOKE_PROBLEM`` + ``make_live``) that turns the kernel into a live
objective the recorder (``core.record``) can measure: compiled for the chip
on a TPU, in interpret mode elsewhere.

``KERNELS``/``get_kernel`` is the registry the record→merge→replay pipeline
resolves kernels through: every registered kernel is a simulation scenario —
record it once (live on CPU/device or via a cost model), then replay the
cache through thousands of hypertuning campaigns.

The ``HUB_KERNELS``/``FRAMEWORK_KERNELS`` tiers only say how each kernel's
hub data is *produced*: hub-tier spaces are brute-forced across all six
device models by ``build_hub``; framework-tier kernels enter the hub as
recorded campaigns (their committed ``SMOKE_PROBLEM`` interpret-mode
entries, plus whatever the scenario fleet records). All six are equally
first-class to lookup — any (kernel, shape, device) triple the hub lacks
a measurement for is served by the roofline surrogate
(``repro.scenarios``).
"""
from __future__ import annotations

import dataclasses
import inspect
from types import ModuleType
from typing import Callable, Mapping

from ..core.costmodel import KernelWorkload
from ..core.devices import live_device
from ..core.searchspace import SearchSpace
from . import (convolution, dedispersion, flash_attention, gemm, hotspot,
               ssd)

# registry used by the hub builder and the autotune layer
HUB_KERNELS = {
    "dedispersion": dedispersion,
    "convolution": convolution,
    "hotspot": hotspot,
    "gemm": gemm,
}

FRAMEWORK_KERNELS = {
    "flash_attention": flash_attention,
    "ssd": ssd,
}

ALL_KERNELS = {**HUB_KERNELS, **FRAMEWORK_KERNELS}


def _accepted(fn: Callable, problem: Mapping) -> dict:
    """Restrict a problem dict to the keyword arguments ``fn`` declares —
    problem dicts carry the union of space/workload/input sizes (e.g. flash
    attention's ``space(seq, d)`` vs its ``workload(bh, seq, d)``)."""
    params = inspect.signature(fn).parameters
    return {k: v for k, v in problem.items() if k in params}


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """Registry view of one kernel module for the recording pipeline.

    ``problem`` dicts override the module's ``SMOKE_PROBLEM`` (the
    CPU-interpret-affordable default); constraints that depend on problem
    sizes (divisibility, halo fit) adapt because the module's ``space()``
    is re-invoked with the resolved sizes.
    """

    name: str
    module: ModuleType
    tier: str  # "hub" | "framework"

    def problem(self, overrides: Mapping | None = None) -> dict:
        return {**self.module.SMOKE_PROBLEM, **(overrides or {})}

    def space(self, problem: Mapping | None = None) -> SearchSpace:
        p = self.problem(problem)
        return self.module.space(**_accepted(self.module.space, p))

    def workload(self, problem: Mapping | None = None) -> KernelWorkload:
        p = self.problem(problem)
        return self.module.workload(**_accepted(self.module.workload, p))

    def make_live(self, problem: Mapping | None = None,
                  interpret: bool | None = None) -> Callable:
        """``fn(config_dict)`` over fixed inputs, for a ``LiveRunner``.
        ``interpret`` defaults to the platform's (``devices.live_device``):
        compiled on a TPU, interpret mode elsewhere. Built inside the
        worker that uses it (the closure holds jax arrays and is not
        picklable)."""
        if interpret is None:
            _, interpret = live_device()
        return self.module.make_live(self.problem(problem), interpret)


KERNELS: dict[str, KernelSpec] = {
    name: KernelSpec(name, mod,
                     "hub" if name in HUB_KERNELS else "framework")
    for name, mod in ALL_KERNELS.items()
}


def get_kernel(name: str) -> KernelSpec:
    try:
        return KERNELS[name]
    except KeyError:
        raise KeyError(
            f"unknown kernel {name!r}; registered: {sorted(KERNELS)}")
