"""Mamba2 SSD (state-space duality) chunked scan — TPU Pallas kernel.

The attention-free hot spot for mamba2/zamba2. Implements the SSD chunked
algorithm (Dao & Gu, arXiv:2405.21060) for one head group:

    h_t = exp(dt_t·A) · h_{t-1} + dt_t · B_t ⊗ x_t          (state update)
    y_t = C_t · h_t                                          (readout)

Chunked over the sequence: within a chunk of Q steps the output splits into
an *intra-chunk* quadratic term ((C Bᵀ) ∘ decay-mask) X — two MXU matmuls —
and an *inter-chunk* term C · (decay · h_in); the carried state is updated
with a third matmul. The chunk loop is the innermost ("arbitrary") grid dim
with the state in VMEM scratch — the TPU-native replacement for the paper's
GPU warp-level scan.

Tunables: chunk length Q, state block, accumulate dtype.
"""
from __future__ import annotations

import functools
from typing import Mapping

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.costmodel import KernelWorkload, alignment_eff
from ..core.devices import DeviceModel
from ..core.searchspace import SearchSpace
from ..core.tunable import Constraint, tunables_from_dict

# Recording problem size (CPU interpret-mode live tuning)
SMOKE_PROBLEM = {"bh": 4, "seq": 256, "p": 32, "n": 32}


def _ssd_kernel(x_ref, dt_row_ref, dt_col_ref, a_ref, b_ref, c_ref, y_ref,
                h_ref, *, chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    x = x_ref[0].astype(jnp.float32)            # (Q, P)
    dt_row = dt_row_ref[0, pl.ds(ci, 1), :]     # (1, Q)
    dt_col = dt_col_ref[0]                      # (Q, 1)
    a = a_ref[0]                                # (1, 1) scalar A (negative)
    b = b_ref[0].astype(jnp.float32)            # (Q, N)
    c = c_ref[0].astype(jnp.float32)            # (Q, N)

    # within-chunk prefix sums of the log per-step decay dt·A, as matmuls
    # with triangular masks: Mosaic lowers no cumsum, and broadcasts only
    # along one of sublanes/lanes at a time, so dt arrives both as a row
    # and as a column. cum_i[i, :] = cum_i and cum_j[:, j] = cum_j.
    hi = jax.lax.Precision.HIGHEST
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = iota_i >= iota_j
    ld_col = dt_col * a
    cum_i = jax.lax.dot(lower.astype(jnp.float32),
                        jnp.broadcast_to(ld_col, (chunk, chunk)),
                        precision=hi, preferred_element_type=jnp.float32)
    cum_j = jax.lax.dot(jnp.broadcast_to(dt_row * a, (chunk, chunk)),
                        (iota_i <= iota_j).astype(jnp.float32),
                        precision=hi, preferred_element_type=jnp.float32)
    cum = cum_i[:, :1]                          # (Q, 1)
    total = cum_j[:1, chunk - 1:]               # (1, 1)
    # intra-chunk: mask[i,j] = exp(cum_i - cum_j) for j <= i (strict decay
    # between step j and i), scaled by dt_j
    decay_ij = jnp.where(lower, jnp.exp(cum_i - cum_j), 0.0)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q, Q)
    w = cb * decay_ij * dt_row
    y_intra = jax.lax.dot(w, x, preferred_element_type=jnp.float32)

    # inter-chunk: y_inter_i = exp(cum_i) * C_i · h_in
    h_in = h_ref[...]                           # (N, P)
    y_inter = jnp.exp(cum) * jax.lax.dot(
        c, h_in, preferred_element_type=jnp.float32)

    y_ref[0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h_out = exp(total) * h_in + Σ_j exp(total - cum_j)·dt_j·B_j⊗X_j
    suffix = jnp.exp(total - cum) * dt_col      # (Q, 1)
    bx = jax.lax.dot_general(b * suffix, x, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (N, P)
    # exp(total) as an (N, 1) column: a (1, 1) -> (N, P) broadcast would
    # cross sublanes and lanes at once
    decay_n = jnp.exp(jax.lax.dot(
        jnp.ones((h_in.shape[0], chunk), jnp.float32), ld_col,
        precision=hi, preferred_element_type=jnp.float32))
    h_ref[...] = decay_n * h_in + bx


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, *, chunk: int = 128,
             interpret: bool = False) -> jax.Array:
    """SSD scan for flattened (batch·heads) leading dim.

    x: (BH, L, P); dt: (BH, L); a: (BH,); b/c: (BH, L, N). Returns y like x.
    ``dt`` and ``a`` are passed in blocks whose last two dimensions equal
    the array's, which the TPU's (8, 128) tiling rule accepts at any chunk.
    """
    bh, l, p = x.shape
    n = b.shape[-1]
    assert l % chunk == 0
    n_chunks = l // chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bh, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, n_chunks, chunk), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((1, chunk, 1), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, 1, 1), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((1, chunk, n), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, chunk, n), lambda h, i: (h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, p), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt.reshape(bh, n_chunks, chunk), dt.reshape(bh, l, 1),
      a.reshape(bh, 1, 1), b, c)


# -------------------------------------------------------------------- ref
def ssd_ref(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
            c: jax.Array, **_unused) -> jax.Array:
    """Sequential oracle: literal recurrence, step by step."""
    bh, l, p = x.shape
    n = b.shape[-1]

    def step(h, inputs):
        x_t, dt_t, b_t, c_t = inputs  # (BH,P), (BH,), (BH,N), (BH,N)
        decay = jnp.exp(dt_t * a)     # (BH,)
        h = (decay[:, None, None] * h
             + dt_t[:, None, None] * b_t[:, :, None] * x_t[:, None, :])
        y_t = jnp.einsum("bnp,bn->bp", h, c_t)
        return h, y_t

    h0 = jnp.zeros((bh, n, p), jnp.float32)
    xs = (x.transpose(1, 0, 2).astype(jnp.float32),
          dt.transpose(1, 0).astype(jnp.float32),
          b.transpose(1, 0, 2).astype(jnp.float32),
          c.transpose(1, 0, 2).astype(jnp.float32))
    _, ys = jax.lax.scan(step, h0, xs)
    return ys.transpose(1, 0, 2).astype(x.dtype)


# ------------------------------------------------------------ search space
def make_live(problem: Mapping | None, interpret: bool):
    """Recorder callable: chunked SSD scan on fixed inputs; state_block and
    accumulator-dtype tunables are cost-model-only."""
    p = {**SMOKE_PROBLEM, **(problem or {})}
    ks = jax.random.split(jax.random.PRNGKey(p.get("seed", 9)), 5)
    bh, l = p["bh"], p["seq"]
    x = jax.random.normal(ks[0], (bh, l, p["p"]), jnp.float32)
    dt = jax.random.uniform(ks[1], (bh, l), jnp.float32, 0.001, 0.1)
    a = -jax.random.uniform(ks[2], (bh,), jnp.float32, 0.5, 1.5)
    b = jax.random.normal(ks[3], (bh, l, p["n"]), jnp.float32)
    c = jax.random.normal(ks[4], (bh, l, p["n"]), jnp.float32)

    def fn(conf: Mapping) -> None:
        out = ssd_scan(x, dt, a, b, c, chunk=conf["chunk"],
                       interpret=interpret)
        jax.block_until_ready(out)

    return fn


def space(seq: int = 4096) -> SearchSpace:
    tunables = tunables_from_dict({
        "chunk": (32, 64, 128, 256, 512),
        "acc_dtype": ("f32", "bf16"),
        "state_block": (32, 64, 128),
    })
    constraints = (
        Constraint(lambda c: seq % c["chunk"] == 0, "chunk divides L"),
        Constraint(lambda c: c["state_block"] <= 128, "state fits a tile"),
    )
    return SearchSpace(tunables, constraints, name="ssd")


def workload(bh: int = 24 * 8, seq: int = 4096, p: int = 64,
             n: int = 128) -> KernelWorkload:
    def flops(c: Mapping) -> float:
        q = c["chunk"]
        per_chunk = 2 * q * q * n + 2 * q * q * p + 4 * q * n * p
        return bh * (seq // q) * per_chunk

    def hbm_bytes(c: Mapping, dev: DeviceModel) -> float:
        return bh * seq * (p + 2 * n + 1) * 2 * 2  # in+out streams, bf16

    def vmem_bytes(c: Mapping) -> float:
        q = c["chunk"]
        acc = 4 if c["acc_dtype"] == "f32" else 2
        return (2 * (q * p + 2 * q * n + q) * 2 + q * q * acc + n * p * 4
                + q * p * acc)

    def grid_size(c: Mapping) -> float:
        return bh * (seq // c["chunk"])

    def compute_eff(c: Mapping, dev: DeviceModel) -> float:
        q = c["chunk"]
        eff = alignment_eff(q, dev.mxu) * alignment_eff(n, dev.lane)
        eff *= min(1.0, q / dev.mxu) ** 0.5
        if c["acc_dtype"] == "bf16":
            eff *= 0.93
        eff *= {32: 0.9, 64: 1.0, 128: 1.0}[c["state_block"]]
        return 0.7 * eff  # cumsum/exp VPU work between matmuls

    return KernelWorkload("ssd", flops, hbm_bytes, vmem_bytes, grid_size,
                          compute_eff)
