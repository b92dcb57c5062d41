"""Hotspot — thermal simulation stencil (benchmark-hub kernel, Rodinia).

Iteratively solves T' = T + dt·(power + conduction(5-point stencil)). The
classic GPU tuning axis is *temporal blocking* (ghost-zone / pyramid
blocking): fuse ``t_block`` timesteps per kernel launch, reading a halo of
``t_block`` cells and recomputing the shrinking pyramid in registers/VMEM —
trading redundant compute for HBM round-trips. That insight carries to TPU
directly: the strip lives in VMEM, the pyramid shrinks by 2 rows/cols per
fused step, HBM traffic drops ~t_block×.

Tunables: strip_h, block_w (spatial tile), t_block (temporal fusion).
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
from ..core.tunable import Constraint, tunables_from_dict

HUB_H, HUB_W = 4096, 4096
HUB_STEPS = 16           # timesteps per hub measurement
BYTES = 4                # fp32 grids

# Recording problem size (CPU interpret-mode live tuning)
SMOKE_PROBLEM = {"h": 64, "w": 128}
# physical coefficients (Rodinia-style, folded constants)
C_CENTER, C_NEIGH, C_POWER = 0.6, 0.1, 0.5


def _stencil_once(t, p):
    """One step on an (r, c) block; returns (r-2, c-2) interior."""
    interior = t[1:-1, 1:-1]
    neigh = (t[:-2, 1:-1] + t[2:, 1:-1] + t[1:-1, :-2] + t[1:-1, 2:])
    return (C_CENTER * interior + C_NEIGH * neigh
            + C_POWER * p[1:-1, 1:-1])


# ----------------------------------------------------------------- kernel
def _hotspot_kernel(t_ref, p_ref, out_ref, *, t_block: int, strip_h: int,
                    block_w: int):
    # t_ref/p_ref: (1, strip_h + 2*t_block, block_w + 2*t_block)
    t = t_ref[0].astype(jnp.float32)
    p = p_ref[0].astype(jnp.float32)
    for _ in range(t_block):
        t = _stencil_once(t, p)
        p = p[1:-1, 1:-1]
    out_ref[0, ...] = t.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("strip_h", "block_w", "t_block",
                                             "interpret"))
def hotspot(temp: jax.Array, power: jax.Array, *, strip_h: int = 64,
            block_w: int = 256, t_block: int = 1,
            interpret: bool = False) -> jax.Array:
    """Advance the thermal grid by ``t_block`` fused steps (periodic BC).

    With periodic boundaries, ghost-zone temporal blocking is *exact*: halo
    cells hold true step-0 neighbor data and the shrinking pyramid recomputes
    the evolution, so fused == sequential everywhere."""
    h, w = temp.shape
    assert h % strip_h == 0 and w % block_w == 0
    halo = t_block
    tp = jnp.pad(temp, halo, mode="wrap")
    pp = jnp.pad(power, halo, mode="wrap")

    def strip_tiles(a):
        n_i, n_j = h // strip_h, w // block_w
        ii, jj = jnp.meshgrid(jnp.arange(n_i), jnp.arange(n_j), indexing="ij")
        def take(i, j):
            return jax.lax.dynamic_slice(
                a, (i * strip_h, j * block_w),
                (strip_h + 2 * halo, block_w + 2 * halo))
        return jax.vmap(jax.vmap(take))(ii, jj).reshape(
            n_i * n_j, strip_h + 2 * halo, block_w + 2 * halo)

    ts, ps = strip_tiles(tp), strip_tiles(pp)
    kernel = functools.partial(_hotspot_kernel, t_block=t_block,
                               strip_h=strip_h, block_w=block_w)
    n_tiles = (h // strip_h) * (w // block_w)
    out = pl.pallas_call(
        kernel,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((1, strip_h + 2 * halo, block_w + 2 * halo),
                         lambda i: (i, 0, 0)),
            pl.BlockSpec((1, strip_h + 2 * halo, block_w + 2 * halo),
                         lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, strip_h, block_w), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles, strip_h, block_w), temp.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(ts, ps)
    n_i, n_j = h // strip_h, w // block_w
    return (out.reshape(n_i, n_j, strip_h, block_w)
               .transpose(0, 2, 1, 3).reshape(h, w))


# -------------------------------------------------------------------- ref
def hotspot_ref(temp: jax.Array, power: jax.Array, *, t_block: int = 1,
                **_unused) -> jax.Array:
    """Pure-jnp oracle: t_block edge-padded stencil steps."""
    t = temp.astype(jnp.float32)
    p = power.astype(jnp.float32)
    for _ in range(t_block):
        tp = jnp.pad(t, 1, mode="wrap")
        pp = jnp.pad(p, 1, mode="wrap")
        t = _stencil_once(tp, pp)
    return t.astype(temp.dtype)


# ----------------------------------------------------------- live recording
def make_live(problem: Mapping | None, interpret: bool):
    """Recorder callable: ``t_block`` fused stencil steps on a fixed grid.
    Constraints bound to the problem size (divisibility, pyramid halo) are
    enforced by ``space(h, w)``; dtype/grid-order tunables are
    cost-model-only."""
    p = {**SMOKE_PROBLEM, **(problem or {})}
    t = jax.random.normal(jax.random.PRNGKey(p.get("seed", 3)),
                          (p["h"], p["w"]), jnp.float32)
    pw = jax.random.normal(jax.random.PRNGKey(p.get("seed", 3) + 1),
                           (p["h"], p["w"]), jnp.float32) * 0.1

    def fn(conf: Mapping) -> None:
        out = hotspot(t, pw, strip_h=conf["strip_h"], block_w=conf["block_w"],
                      t_block=conf["t_block"], interpret=interpret)
        jax.block_until_ready(out)

    return fn


# ------------------------------------------------------------ search space
def space(h: int = HUB_H, w: int = HUB_W) -> SearchSpace:
    tunables = tunables_from_dict({
        "strip_h": (8, 16, 32, 64, 128, 256, 512, 1024),
        "block_w": (128, 256, 512, 1024, 2048, 4096),
        "io_dtype": ("f32", "bf16"),
        "t_block": tuple(range(1, 17)),
        "acc_dtype": ("f32", "bf16"),
        "grid_order": ("row", "col"),
    })
    constraints = (
        Constraint(lambda c: h % c["strip_h"] == 0, "strip_h divides H"),
        Constraint(lambda c: w % c["block_w"] == 0, "block_w divides W"),
        Constraint(lambda c: 2 * c["t_block"] < c["strip_h"],
                   "pyramid halo must fit the strip"),
    )
    return SearchSpace(tunables, constraints, name="hotspot")


# -------------------------------------------------------------- cost model
def workload(h: int = HUB_H, w: int = HUB_W,
             steps: int = HUB_STEPS) -> KernelWorkload:
    def flops(c: Mapping) -> float:
        tb, sh, bw = c["t_block"], c["strip_h"], c["block_w"]
        # redundant pyramid compute: each fused step s processes
        # (sh + 2(tb-s))×(bw + 2(tb-s)) instead of sh×bw
        per_tile = sum((sh + 2 * (tb - s)) * (bw + 2 * (tb - s))
                       for s in range(1, tb + 1))
        n_tiles = (h // sh) * (w // bw)
        launches = -(-steps // tb)
        return 8.0 * per_tile * n_tiles * launches

    def hbm_bytes(c: Mapping, dev: DeviceModel) -> float:
        tb, sh, bw = c["t_block"], c["strip_h"], c["block_w"]
        halo_factor = ((sh + 2 * tb) / sh) * ((bw + 2 * tb) / bw)
        blk = (sh + 2 * tb) * (bw + 2 * tb) * BYTES
        byt = BYTES if c["io_dtype"] == "f32" else 2
        per_launch = (h * w * byt * 2 * halo_factor / dma_eff(blk)
                      + h * w * byt / dma_eff(sh * bw * byt))
        return per_launch * -(-steps // tb)

    def vmem_bytes(c: Mapping) -> float:
        tb, sh, bw = c["t_block"], c["strip_h"], c["block_w"]
        blk = (sh + 2 * tb) * (bw + 2 * tb) * BYTES
        return 2 * (2 * blk + sh * bw * BYTES) + blk  # T,P in, out, scratch

    def grid_size(c: Mapping) -> float:
        return ((h // c["strip_h"]) * (w // c["block_w"])
                * -(-steps // c["t_block"]))

    def compute_eff(c: Mapping, dev: DeviceModel) -> float:
        eff = (alignment_eff(c["strip_h"], dev.sublane)
               * alignment_eff(c["block_w"], dev.lane))
        eff *= 0.11  # VPU-bound stencil
        if c["acc_dtype"] == "bf16":
            eff *= 1.05
        if c["io_dtype"] == "bf16":
            eff *= 0.97  # conversion cost (but traffic halves)
        if c["grid_order"] == "col":
            eff *= 0.95
        return eff

    return KernelWorkload("hotspot", flops, hbm_bytes, vmem_bytes, grid_size,
                          compute_eff)
