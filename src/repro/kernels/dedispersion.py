"""Dedispersion — radio-astronomy signal reconstruction (benchmark-hub kernel).

out[dm, t] = Σ_c x[c, t + delay[c, dm]] — a bandwidth-bound gather-reduce.
GPU implementations tune thread tiles over (dm, time) and channel chunking;
the TPU adaptation tiles (dm, time) over the grid with the channel loop
inside the kernel: each (channel, dm) pair rotates its VMEM-resident
channel row by the delay (a lane rotation — Mosaic lowers no unaligned
dynamic lane slice) and accumulates the leading ``block_t`` lanes. The
delay table is precomputed (as real pipelines do) and scalar-prefetched
into SMEM.

Tunables: block_dm, block_t (output tile), chan_chunk (channels per inner
accumulation round), delay layout.
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

# Hub problem: 256 channels, 16384 samples, 256 dispersion measures
HUB_NCHAN, HUB_NTIME, HUB_NDM = 256, 16384, 256
BYTES = 4
MAX_DELAY = 512  # delay table values are in [0, MAX_DELAY)

# Recording problem size (CPU interpret-mode live tuning); ntime includes
# the MAX_DELAY halo the wrapper slices off
SMOKE_PROBLEM = {"nchan": 32, "ntime": 768 + MAX_DELAY, "ndm": 24}


def make_delays(nchan: int = HUB_NCHAN, ndm: int = HUB_NDM,
                max_delay: int = MAX_DELAY) -> jax.Array:
    """Quadratic-in-frequency dispersion delays (int32), shape (nchan, ndm)."""
    c = jnp.arange(nchan, dtype=jnp.float32)[:, None] / nchan
    d = jnp.arange(ndm, dtype=jnp.float32)[None, :] / ndm
    delays = (max_delay - 1) * d * (1.0 / (0.25 + 0.75 * (1 - c)) ** 2 - 1.0) / 15.0
    return jnp.clip(delays.astype(jnp.int32), 0, max_delay - 1)


# ----------------------------------------------------------------- kernel
def _dedisp_kernel(delay_ref, x_ref, out_ref, acc_ref, *, nchan: int,
                   block_dm: int, block_t: int):
    # delay_ref: (nchan, ndm) in SMEM; x_ref: (1, nchan, block_t + MAX_DELAY)
    # out_ref/acc_ref: (block_dm, block_t)
    dm0 = pl.program_id(0) * block_dm
    width = block_t + MAX_DELAY
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def chan_body(c, carry):
        row = x_ref[0, pl.ds(c, 1), :]  # (1, width)

        def dm_body(i, carry):
            off = delay_ref[c, dm0 + i]
            seg = pltpu.roll(row, (width - off) % width, 1)[:, :block_t]
            acc_ref[pl.ds(i, 1), :] += seg.astype(jnp.float32)
            return carry

        return jax.lax.fori_loop(0, block_dm, dm_body, carry)

    jax.lax.fori_loop(0, nchan, chan_body, 0)
    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_dm", "block_t", "interpret"))
def dedisperse(x: jax.Array, delays: jax.Array, *, block_dm: int = 32,
               block_t: int = 512, interpret: bool = False) -> jax.Array:
    """x: (nchan, ntime) padded so gathers stay in range; delays: (nchan, ndm).

    Output: (ndm, ntime - MAX_DELAY).
    """
    nchan, ntime = x.shape
    nchan2, ndm = delays.shape
    assert nchan == nchan2
    nt_out0 = ntime - MAX_DELAY
    ndm0 = ndm
    nt_out = -(-nt_out0 // block_t) * block_t
    ndm = -(-ndm // block_dm) * block_dm
    if nt_out != nt_out0:
        x = jnp.pad(x, ((0, 0), (0, nt_out - nt_out0)))
    if ndm != ndm0:
        delays = jnp.pad(delays, ((0, 0), (0, ndm - ndm0)))

    # pre-tile time strips with MAX_DELAY halo (BlockSpecs cannot overlap)
    n_t = nt_out // block_t
    strips = jax.vmap(
        lambda j: jax.lax.dynamic_slice(
            x, (0, j * block_t), (nchan, block_t + MAX_DELAY))
    )(jnp.arange(n_t))  # (n_t, nchan, block_t + MAX_DELAY)

    kernel = functools.partial(_dedisp_kernel, nchan=nchan, block_dm=block_dm,
                               block_t=block_t)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(ndm // block_dm, n_t),
            in_specs=[pl.BlockSpec((1, nchan, block_t + MAX_DELAY),
                                   lambda i, j, d: (j, 0, 0))],
            out_specs=pl.BlockSpec((block_dm, block_t),
                                   lambda i, j, d: (i, j)),
            scratch_shapes=[pltpu.VMEM((block_dm, block_t), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((ndm, nt_out), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(delays, strips)[:ndm0, :nt_out0]


# -------------------------------------------------------------------- ref
def dedisperse_ref(x: jax.Array, delays: jax.Array, **_unused) -> jax.Array:
    """Pure-jnp oracle."""
    nchan, ntime = x.shape
    _, ndm = delays.shape
    nt_out = ntime - MAX_DELAY
    t_idx = jnp.arange(nt_out)

    def one_dm(dm):
        # sum over channels of x[c, t + delay[c, dm]]
        idx = t_idx[None, :] + delays[:, dm][:, None]  # (nchan, nt_out)
        gathered = jnp.take_along_axis(x, idx, axis=1)
        return gathered.astype(jnp.float32).sum(axis=0)

    out = jax.vmap(one_dm)(jnp.arange(ndm))
    return out.astype(x.dtype)


# ----------------------------------------------------------- live recording
def make_live(problem: Mapping | None, interpret: bool):
    """Recorder callable: fixed signal + delay table; chan_chunk/layout/
    unroll tunables are cost-model-only."""
    p = {**SMOKE_PROBLEM, **(problem or {})}
    x = jax.random.normal(jax.random.PRNGKey(p.get("seed", 5)),
                          (p["nchan"], p["ntime"]), jnp.float32)
    delays = make_delays(p["nchan"], p["ndm"])

    def fn(conf: Mapping) -> None:
        out = dedisperse(x, delays, block_dm=conf["block_dm"],
                         block_t=conf["block_t"], interpret=interpret)
        jax.block_until_ready(out)

    return fn


# ------------------------------------------------------------ search space
def space(nchan: int = HUB_NCHAN, ntime: int = HUB_NTIME,
          ndm: int = HUB_NDM) -> SearchSpace:
    nt_out = ntime - MAX_DELAY
    tunables = tunables_from_dict({
        "block_dm": (1, 2, 4, 8, 12, 16, 24, 32, 48, 64, 96, 128),
        "block_t": (128, 192, 256, 384, 512, 768, 1024, 1536, 2048, 3968),
        "chan_chunk": (8, 16, 32, 64, 128, 256),
        "delay_layout": ("dm_major", "chan_major"),
        "time_unroll": (1, 2, 4),
    })
    constraints = (
        Constraint(lambda c: nchan % c["chan_chunk"] == 0,
                   "chan_chunk divides channels"),
    )
    return SearchSpace(tunables, constraints, name="dedispersion")


# -------------------------------------------------------------- cost model
def workload(nchan: int = HUB_NCHAN, ntime: int = HUB_NTIME,
             ndm: int = HUB_NDM) -> KernelWorkload:
    nt_out = ntime - MAX_DELAY

    def _padded(c: Mapping):
        bdm, bt = c["block_dm"], c["block_t"]
        return (-(-ndm // bdm) * bdm, -(-nt_out // bt) * bt)

    def flops(c: Mapping) -> float:
        ndm_p, nt_p = _padded(c)
        return 1.0 * nchan * ndm_p * nt_p  # adds only

    def hbm_bytes(c: Mapping, dev: DeviceModel) -> float:
        bt = c["block_t"]
        ndm_p, nt_p = _padded(c)
        # channel block re-read per dm-tile; halo MAX_DELAY per time tile
        n_dm_tiles = ndm_p // c["block_dm"]
        x_blk = nchan * (bt + MAX_DELAY) * BYTES
        x_reads = (nchan * (bt + MAX_DELAY) * BYTES * n_dm_tiles
                   * (nt_p // bt) / dma_eff(x_blk))
        out_write = ndm_p * nt_p * BYTES / dma_eff(
            c["block_dm"] * c["block_t"] * BYTES)
        delay_reads = nchan * ndm_p * 4
        return x_reads + out_write + delay_reads

    def vmem_bytes(c: Mapping) -> float:
        bdm, bt = c["block_dm"], c["block_t"]
        x_blk = nchan * (bt + MAX_DELAY) * BYTES
        return 2 * (x_blk + nchan * bdm * 4) + bdm * bt * (4 + BYTES)

    def grid_size(c: Mapping) -> float:
        ndm_p, nt_p = _padded(c)
        return (ndm_p // c["block_dm"]) * (nt_p // c["block_t"])

    def compute_eff(c: Mapping, dev: DeviceModel) -> float:
        eff = (alignment_eff(c["block_dm"], dev.sublane)
               * alignment_eff(c["block_t"], dev.lane))
        eff *= 0.08  # gather-bound VPU kernel
        # larger chan chunks amortize loop control until VREG pressure bites
        eff *= {8: 0.8, 16: 0.9, 32: 1.0, 64: 1.0, 128: 0.93, 256: 0.85}[
            c["chan_chunk"]]
        if c["delay_layout"] == "chan_major":
            eff *= 0.97
        eff *= {1: 0.95, 2: 1.0, 4: 0.98}[c["time_unroll"]]
        return eff

    return KernelWorkload("dedispersion", flops, hbm_bytes, vmem_bytes,
                          grid_size, compute_eff)
