"""GEMM — C = alpha·A·B + beta·C (benchmark-hub kernel, CLBlast analogue).

Pallas TPU kernel with tunable BlockSpec tiling (block_m/n/k) and grid order.
The MXU wants 128-aligned tiles; the search space deliberately includes
misaligned and VMEM-overflowing configurations, because real auto-tuning
spaces contain them (the cost model penalizes/invalidates those, the Pallas
kernel itself is validated on the aligned subset in interpret mode).

TPU adaptation of the paper's GPU GEMM space: instead of threads-per-block /
shared-memory staging, the tunables are VMEM tile shapes and the K-loop
placement (innermost "arbitrary" grid dim accumulating into a VMEM scratch).
"""
from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.costmodel import KernelWorkload, alignment_eff, dma_eff
from ..core.devices import DeviceModel
from ..core.searchspace import SearchSpace
from ..core.tunable import tunables_from_dict

# Hub problem size (dense square GEMM, bf16 in / fp32 accumulate)
HUB_M, HUB_N, HUB_K = 4096, 4096, 4096
BYTES = 2  # bf16

# Recording problem size: small enough that a CPU interpret-mode evaluation
# takes milliseconds, so live-recording a tuning run is affordable
SMOKE_PROBLEM = {"m": 128, "n": 128, "k": 128}


# ----------------------------------------------------------------- kernel
def _gemm_kernel(a_ref, b_ref, c0_ref, out_ref, acc_ref, *, n_k: int,
                 alpha: float, beta: float):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _emit():
        out_ref[...] = (alpha * acc_ref[...]
                        + beta * c0_ref[...].astype(jnp.float32)
                        ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "alpha", "beta", "interpret"))
def gemm(a: jax.Array, b: jax.Array, c0: jax.Array, *, block_m: int = 128,
         block_n: int = 128, block_k: int = 128, alpha: float = 1.0,
         beta: float = 1.0, interpret: bool = False) -> jax.Array:
    """Tiled Pallas GEMM. Non-dividing blocks are zero-padded (and the
    padding waste is what the cost model charges for them)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2 and c0.shape == (m, n)
    m0, n0 = m, n
    mp = -(-m // block_m) * block_m
    np_ = -(-n // block_n) * block_n
    kp = -(-k // block_k) * block_k
    if (mp, np_, kp) != (m, n, k):
        a = jnp.pad(a, ((0, mp - m), (0, kp - k)))
        b = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
        c0 = jnp.pad(c0, ((0, mp - m), (0, np_ - n)))
    m, n, k = mp, np_, kp
    n_k = k // block_k
    kernel = functools.partial(_gemm_kernel, n_k=n_k, alpha=alpha, beta=beta)
    return pl.pallas_call(
        kernel,
        grid=(m // block_m, n // block_n, n_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, c0)[:m0, :n0]


# -------------------------------------------------------------------- ref
def gemm_ref(a: jax.Array, b: jax.Array, c0: jax.Array, *, alpha: float = 1.0,
             beta: float = 1.0, **_unused) -> jax.Array:
    """Pure-jnp oracle."""
    acc = jnp.dot(a, b, preferred_element_type=jnp.float32)
    return (alpha * acc + beta * c0.astype(jnp.float32)).astype(a.dtype)


# ----------------------------------------------------------- live recording
def make_live(problem: Mapping | None, interpret: bool):
    """Evaluation callable for the recorder: fixed inputs,
    ``fn(config_dict)`` runs the Pallas kernel with that tiling (in
    interpret mode when ``interpret``) and blocks until ready. Tunables
    the TPU wrapper does not consume (grid order, accumulator dtype) are
    cost-model-only and ignored here."""
    p = {**SMOKE_PROBLEM, **(problem or {})}
    ks = jax.random.split(jax.random.PRNGKey(p.get("seed", 0)), 3)
    a = jax.random.normal(ks[0], (p["m"], p["k"]), jnp.float32).astype(jnp.bfloat16)
    b = jax.random.normal(ks[1], (p["k"], p["n"]), jnp.float32).astype(jnp.bfloat16)
    c0 = jax.random.normal(ks[2], (p["m"], p["n"]), jnp.float32).astype(jnp.bfloat16)

    def fn(conf: Mapping) -> None:
        out = gemm(a, b, c0, block_m=conf["block_m"], block_n=conf["block_n"],
                   block_k=conf["block_k"], interpret=interpret)
        jax.block_until_ready(out)

    return fn


# ------------------------------------------------------------ search space
def space(m: int = HUB_M, n: int = HUB_N, k: int = HUB_K) -> SearchSpace:
    tunables = tunables_from_dict({
        "block_m": (8, 16, 24, 32, 48, 64, 96, 128, 160, 192, 256, 320, 384,
                    448, 512),
        "block_n": (64, 96, 128, 160, 192, 256, 320, 384, 512, 640, 768, 896,
                    1024),
        "block_k": (32, 48, 64, 96, 128, 192, 256, 384, 512, 768, 1024, 1536,
                    2048),
        "grid_order": ("mn", "nm"),          # output-stationary sweep order
        "acc_dtype": ("f32", "bf16"),        # accumulator precision
    })
    # non-dividing blocks are legal (zero-padded) — the padding waste is
    # costed, so the space is rich in mediocre configurations, like real
    # auto-tuning spaces.
    return SearchSpace(tunables, (), name="gemm")


# -------------------------------------------------------------- cost model
def workload(m: int = HUB_M, n: int = HUB_N, k: int = HUB_K) -> KernelWorkload:
    def _padded(c: Mapping):
        bm, bn, bk = c["block_m"], c["block_n"], c["block_k"]
        return (-(-m // bm) * bm, -(-n // bn) * bn, -(-k // bk) * bk)

    def flops(c: Mapping) -> float:
        mp, np_, kp = _padded(c)
        return 2.0 * mp * np_ * kp + 3.0 * mp * np_  # incl. padding waste

    def hbm_bytes(c: Mapping, dev: DeviceModel) -> float:
        bm, bn, bk = c["block_m"], c["block_n"], c["block_k"]
        mp, np_, kp = _padded(c)
        # A is re-read for every N-tile, B for every M-tile; C0/out once.
        n_m, n_n = mp // bm, np_ // bn
        a_reads = mp * kp * BYTES * n_n / dma_eff(bm * bk * BYTES)
        b_reads = kp * np_ * BYTES * n_m / dma_eff(bk * bn * BYTES)
        c_traffic = 2 * mp * np_ * BYTES / dma_eff(bm * bn * BYTES)
        return a_reads + b_reads + c_traffic

    def vmem_bytes(c: Mapping) -> float:
        bm, bn, bk = c["block_m"], c["block_n"], c["block_k"]
        acc = 4 if c["acc_dtype"] == "f32" else 2
        # double-buffered in/out blocks + accumulator scratch
        return 2 * (bm * bk + bk * bn + 2 * bm * bn) * BYTES + bm * bn * acc

    def grid_size(c: Mapping) -> float:
        mp, np_, kp = _padded(c)
        return ((mp // c["block_m"]) * (np_ // c["block_n"])
                * (kp // c["block_k"]))

    def compute_eff(c: Mapping, dev: DeviceModel) -> float:
        bm, bn, bk = c["block_m"], c["block_n"], c["block_k"]
        eff = (alignment_eff(bm, dev.sublane)
               * alignment_eff(bn, dev.lane)
               * alignment_eff(bk, dev.lane))
        # MXU likes >= mxu-sized matmul dims; smaller tiles underfill it
        eff *= min(1.0, bm / dev.mxu) ** 0.5
        # bf16 accumulate halves epilogue traffic but costs extra passes on
        # the MXU for large K (numerical chunking): mild penalty
        if c["acc_dtype"] == "bf16":
            eff *= 0.92
        # "nm" order is slightly worse for row-major A prefetch
        if c["grid_order"] == "nm":
            eff *= 0.97
        return eff

    return KernelWorkload("gemm", flops, hbm_bytes, vmem_bytes, grid_size,
                          compute_eff)
