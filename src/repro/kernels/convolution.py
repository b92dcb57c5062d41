"""2-D convolution stencil (benchmark-hub kernel; image filtering).

TPU adaptation: GPU implementations tune threads/block and shared-memory
staging of the halo. On TPU the analogue is **overlap decomposition**: the
input is pre-tiled into row strips *with halo* (a cheap gather done once in
the jit wrapper), so every Pallas program owns an independent (strip_h+fh-1,
W+fw-1) VMEM block and no overlapping BlockSpec is needed. Within a strip the
filter is applied as fh·fw shifted multiply-adds on the VPU, with a tunable
unroll of the filter-row loop and a tunable output column tile.

Tunables: strip_h (rows per program), block_w (output column tile),
unroll_fh (filter-row unroll), accumulate dtype.
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

# Hub problem: 4096×4096 image, 17×17 filter (Kernel Tuner's conv benchmark)
HUB_H, HUB_W, HUB_FH, HUB_FW = 4096, 4096, 17, 17
BYTES = 4  # fp32 image

# Recording problem size (CPU interpret-mode live tuning)
SMOKE_PROBLEM = {"h": 128, "w": 256, "fh": 7, "fw": 7}


# ----------------------------------------------------------------- kernel
def _conv_kernel(x_ref, f_ref, out_ref, *, fh: int, fw: int, block_w: int):
    # x_ref: (1, strip_h+fh-1, block_w+fw-1); out_ref: (1, strip_h, block_w)
    x = x_ref[0]
    sh = out_ref.shape[1]
    acc = jnp.zeros((sh, block_w), jnp.float32)
    for dy in range(fh):
        for dx in range(fw):
            tile = x[dy:dy + sh, dx:dx + block_w]
            acc += tile.astype(jnp.float32) * f_ref[dy, dx]
    out_ref[0, ...] = acc.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("strip_h", "block_w", "interpret"))
def conv2d(x: jax.Array, f: jax.Array, *, strip_h: int = 64,
           block_w: int = 256, interpret: bool = False) -> jax.Array:
    """'Same'-padded 2-D convolution (cross-correlation, like the hub kernel).

    x: (H, W) image; f: (fh, fw) filter. strip_h must divide H, block_w must
    divide W.
    """
    h0, w0 = x.shape
    fh, fw = f.shape
    h = -(-h0 // strip_h) * strip_h
    w = -(-w0 // block_w) * block_w
    ph, pw = fh // 2, fw // 2
    xp = jnp.pad(x, ((ph, fh - 1 - ph + h - h0), (pw, fw - 1 - pw + w - w0)))
    # overlap decomposition: gather patches with halo in both dims (blocks
    # stride by their own shape, so overlapping BlockSpecs are not possible —
    # the halo is materialized once here instead)
    n_i, n_j = h // strip_h, w // block_w
    ii, jj = jnp.meshgrid(jnp.arange(n_i), jnp.arange(n_j), indexing="ij")

    def take(i, j):
        return jax.lax.dynamic_slice(
            xp, (i * strip_h, j * block_w),
            (strip_h + fh - 1, block_w + fw - 1))

    patches = jax.vmap(jax.vmap(take))(ii, jj).reshape(
        n_i * n_j, strip_h + fh - 1, block_w + fw - 1)

    kernel = functools.partial(_conv_kernel, fh=fh, fw=fw, block_w=block_w)
    out = pl.pallas_call(
        kernel,
        grid=(n_i * n_j,),
        in_specs=[
            pl.BlockSpec((1, strip_h + fh - 1, block_w + fw - 1),
                         lambda i: (i, 0, 0)),
            pl.BlockSpec((fh, fw), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, strip_h, block_w), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_i * n_j, strip_h, block_w), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(patches, f)
    return (out.reshape(n_i, n_j, strip_h, block_w)
               .transpose(0, 2, 1, 3).reshape(h, w))[:h0, :w0]


# -------------------------------------------------------------------- ref
def conv2d_ref(x: jax.Array, f: jax.Array, **_unused) -> jax.Array:
    """Pure-jnp oracle: same-padded cross-correlation."""
    fh, fw = f.shape
    ph, pw = fh // 2, fw // 2
    xp = jnp.pad(x, ((ph, fh - 1 - ph), (pw, fw - 1 - pw)))
    acc = jnp.zeros(x.shape, jnp.float32)
    for dy in range(fh):
        for dx in range(fw):
            acc += xp[dy:dy + x.shape[0], dx:dx + x.shape[1]].astype(jnp.float32) * f[dy, dx]
    return acc.astype(x.dtype)


# ----------------------------------------------------------- live recording
def make_live(problem: Mapping | None, interpret: bool):
    """Recorder callable: same-padded conv on a fixed image/filter; the
    unroll/vector/accumulator tunables are cost-model-only."""
    p = {**SMOKE_PROBLEM, **(problem or {})}
    x = jax.random.normal(jax.random.PRNGKey(p.get("seed", 1)),
                          (p["h"], p["w"]), jnp.float32)
    f = jax.random.normal(jax.random.PRNGKey(p.get("seed", 1) + 1),
                          (p["fh"], p["fw"]), jnp.float32)

    def fn(conf: Mapping) -> None:
        out = conv2d(x, f, strip_h=conf["strip_h"], block_w=conf["block_w"],
                     interpret=interpret)
        jax.block_until_ready(out)

    return fn


# ------------------------------------------------------------ search space
def space(h: int = HUB_H, w: int = HUB_W, fh: int = HUB_FH,
          fw: int = HUB_FW) -> SearchSpace:
    tunables = tunables_from_dict({
        "strip_h": (8, 16, 24, 32, 48, 64, 80, 96, 128, 160, 192, 256, 384,
                    512),
        "block_w": (96, 128, 160, 256, 320, 512, 640, 1024, 1280, 2048, 4096),
        "unroll_fh": (1, 2, 4, 8, 17),
        "acc_dtype": ("f32", "bf16"),
        "vector_w": (128, 256, 512),       # VPU vectorization width hint
    })
    constraints = (
        Constraint(lambda c: c["vector_w"] <= c["block_w"],
                   "vector width within column tile"),
    )
    return SearchSpace(tunables, constraints, name="convolution")


# -------------------------------------------------------------- cost model
def workload(h: int = HUB_H, w: int = HUB_W, fh: int = HUB_FH,
             fw: int = HUB_FW) -> KernelWorkload:
    def _padded(c: Mapping):
        sh, bw = c["strip_h"], c["block_w"]
        return (-(-h // sh) * sh, -(-w // bw) * bw)

    def flops(c: Mapping) -> float:
        hp, wp = _padded(c)
        return 2.0 * hp * wp * fh * fw

    def hbm_bytes(c: Mapping, dev: DeviceModel) -> float:
        sh, bw = c["strip_h"], c["block_w"]
        hp, wp = _padded(c)
        # halo duplication in both dims + one write; small patches stream badly
        blk = (sh + fh - 1) * (bw + fw - 1) * BYTES
        reads = hp * wp * BYTES * ((sh + fh - 1) / sh) * ((bw + fw - 1) / bw)
        return reads / dma_eff(blk) + hp * wp * BYTES / dma_eff(sh * bw * BYTES)

    def vmem_bytes(c: Mapping) -> float:
        sh, bw = c["strip_h"], c["block_w"]
        acc = 4 if c["acc_dtype"] == "f32" else 2
        in_blk = (sh + fh - 1) * (bw + fw - 1) * BYTES
        out_blk = sh * bw * BYTES
        return 2 * (in_blk + out_blk) + sh * bw * acc

    def grid_size(c: Mapping) -> float:
        hp, wp = _padded(c)
        return (hp // c["strip_h"]) * (wp // c["block_w"])

    def compute_eff(c: Mapping, dev: DeviceModel) -> float:
        sh, bw = c["strip_h"], c["block_w"]
        eff = alignment_eff(sh, dev.sublane) * alignment_eff(bw, dev.lane)
        # conv runs on the VPU: peak is ~1/8 of MXU peak for this model
        eff *= 0.125
        # loop unrolling amortizes scalar overhead; too much spills
        unroll = c["unroll_fh"]
        eff *= {1: 0.72, 2: 0.85, 4: 1.0, 8: 0.97, 17: 0.88}[unroll]
        if c["acc_dtype"] == "bf16":
            eff *= 1.08  # fewer register bytes, slightly better issue rate
        # vector width: full-lane vectors best
        eff *= {128: 1.0, 256: 0.99, 512: 0.96}[c["vector_w"]]
        return eff

    return KernelWorkload("convolution", flops, hbm_bytes, vmem_bytes,
                          grid_size, compute_eff)
