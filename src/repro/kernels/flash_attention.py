"""Flash attention (forward) — fused online-softmax attention for TPU.

The framework's training/prefill hot spot. Pallas kernel with tunable
``block_q`` × ``block_kv`` VMEM tiling, causal and sliding-window masking,
and GQA (kv-head sharing) via the index map. Fully-masked KV blocks are
skipped through the grid bound, not branches, by iterating only the lower
triangle when causal.

The pure-jnp oracle is the blockwise attention used by the model stack
(models/attention.py implements the same math with lax.scan so the compiled
graph is memory-sublinear in sequence length as well).

Tunables (autotune space): block_q, block_kv, accumulator dtype.
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

NEG_INF = -1e30

# Recording problem size (CPU interpret-mode live tuning): 4 q heads over a
# GQA group of 2, short sequence
SMOKE_PROBLEM = {"bh": 4, "bh_kv": 2, "seq": 256, "d": 64}


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 block_q: int, block_kv: int, n_kv: int, causal: bool,
                 window: int | None, scale: float):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                      # (block_q, d)
    k = k_ref[0]                      # (block_kv, d)
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kv_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = jnp.ones(s.shape, jnp.bool_)
    if causal:
        mask &= q_pos >= kv_pos
    if window is not None:
        mask &= (q_pos - kv_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    acc_ref[...] = (acc_ref[...] * alpha[:, None]
                    + jax.lax.dot(p.astype(v.dtype), v,
                                  preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _emit():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "block_kv", "causal",
                                             "window", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    block_q: int = 128, block_kv: int = 128,
                    causal: bool = True, window: int | None = None,
                    interpret: bool = False) -> jax.Array:
    """q: (BH, S, D); k/v: (BH_kv, S, D) with BH % BH_kv == 0 (GQA).

    Heads are pre-flattened into the leading dim; the kv index map folds the
    GQA group so each q head reads its shared kv head.
    """
    bh, s, d = q.shape
    bh_kv = k.shape[0]
    assert bh % bh_kv == 0
    group = bh // bh_kv
    assert s % block_q == 0 and s % block_kv == 0
    n_kv = s // block_kv
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(_attn_kernel, block_q=block_q,
                               block_kv=block_kv, n_kv=n_kv, causal=causal,
                               window=window, scale=scale)
    return pl.pallas_call(
        kernel,
        grid=(bh, s // block_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_kv, d), lambda h, i, j, g=group: (h // g, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda h, i, j, g=group: (h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


# -------------------------------------------------------------------- ref
def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: int | None = None,
                  **_unused) -> jax.Array:
    """Pure-jnp oracle (materializes S×S — test sizes only)."""
    bh, s, d = q.shape
    bh_kv = k.shape[0]
    group = bh // bh_kv
    kf = jnp.repeat(k, group, axis=0)
    vf = jnp.repeat(v, group, axis=0)
    logits = jnp.einsum("hqd,hkd->hqk", q.astype(jnp.float32),
                        kf.astype(jnp.float32)) / (d ** 0.5)
    q_pos = jnp.arange(s)[:, None]
    kv_pos = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= q_pos >= kv_pos
    if window is not None:
        mask &= (q_pos - kv_pos) < window
    logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p, vf.astype(jnp.float32)).astype(q.dtype)


# ------------------------------------------------------------ search space
def make_live(problem: Mapping | None, interpret: bool):
    """Recorder callable: causal GQA attention on fixed q/k/v; the
    accumulator-dtype tunable is cost-model-only."""
    p = {**SMOKE_PROBLEM, **(problem or {})}
    ks = jax.random.split(jax.random.PRNGKey(p.get("seed", 6)), 3)
    q = jax.random.normal(ks[0], (p["bh"], p["seq"], p["d"]), jnp.float32)
    k = jax.random.normal(ks[1], (p["bh_kv"], p["seq"], p["d"]), jnp.float32)
    v = jax.random.normal(ks[2], (p["bh_kv"], p["seq"], p["d"]), jnp.float32)

    def fn(conf: Mapping) -> None:
        out = flash_attention(q, k, v, block_q=conf["block_q"],
                              block_kv=conf["block_kv"], causal=True,
                              interpret=interpret)
        jax.block_until_ready(out)

    return fn


def space(seq: int = 4096, d: int = 128) -> SearchSpace:
    tunables = tunables_from_dict({
        "block_q": (64, 128, 256, 512, 1024),
        "block_kv": (128, 256, 512, 1024, 2048),
        "acc_dtype": ("f32", "bf16"),
    })
    constraints = (
        Constraint(lambda c: seq % c["block_q"] == 0, "block_q divides S"),
        Constraint(lambda c: seq % c["block_kv"] == 0, "block_kv divides S"),
    )
    return SearchSpace(tunables, constraints, name="flash_attention")


def workload(bh: int = 32, seq: int = 4096, d: int = 128,
             causal: bool = True) -> KernelWorkload:
    frac = 0.5 if causal else 1.0  # causal halves useful work

    def flops(c: Mapping) -> float:
        return 4.0 * bh * seq * seq * d * frac  # qk^T + pv

    def hbm_bytes(c: Mapping, dev: DeviceModel) -> float:
        bq, bkv = c["block_q"], c["block_kv"]
        # k/v streamed once per q block
        kv_reads = 2 * bh * seq * d * 2 * (seq // bq) * frac
        qo = 2 * bh * seq * d * 2
        return kv_reads + qo

    def vmem_bytes(c: Mapping) -> float:
        bq, bkv = c["block_q"], c["block_kv"]
        acc = 4 if c["acc_dtype"] == "f32" else 2
        return (2 * (bq * d + 2 * bkv * d + bq * d) * 2
                + bq * d * acc + bq * bkv * 4 + 2 * bq * 4)

    def grid_size(c: Mapping) -> float:
        return bh * (seq // c["block_q"]) * (seq // c["block_kv"]) * frac

    def compute_eff(c: Mapping, dev: DeviceModel) -> float:
        bq, bkv = c["block_q"], c["block_kv"]
        eff = alignment_eff(bq, dev.mxu) * alignment_eff(bkv, dev.lane)
        eff *= min(1.0, bkv / dev.mxu) ** 0.5
        if c["acc_dtype"] == "bf16":
            eff *= 0.9  # extra rescaling passes
        return 0.75 * eff  # softmax/VPU overhead between the two matmuls

    return KernelWorkload("flash_attention", flops, hbm_bytes, vmem_bytes,
                          grid_size, compute_eff)
