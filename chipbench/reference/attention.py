"""Plain reference for causal attention with grouped KV heads, written
from the definition (softmax(q k^T / sqrt(d)) v, query i sees keys 0..i,
query head h reads KV head h // (heads / kv_heads)); it imports nothing
of the program under test.

Computed one block of query heads at a time, so that the S x S logits of
a long sequence fit beside the program's outputs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def inputs(key_seed: int, bh: int, bh_kv: int, seq: int, d: int):
    """The cell's q, k, v: standard normal float32 from one key, split
    three ways, in the order q, k, v."""
    ks = jax.random.split(jax.random.PRNGKey(key_seed), 3)
    q = jax.random.normal(ks[0], (bh, seq, d), jnp.float32)
    k = jax.random.normal(ks[1], (bh_kv, seq, d), jnp.float32)
    v = jax.random.normal(ks[2], (bh_kv, seq, d), jnp.float32)
    return q, k, v


def _heads(q, k, v, dtype, precision):
    """Attention of a block of query heads over their own KV heads
    (``k``/``v`` already repeated to one per query head)."""
    d = q.shape[-1]
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    logits = jnp.einsum("hqd,hkd->hqk", q, k, precision=precision)
    logits = logits / jnp.asarray(d ** 0.5, dtype)
    s = q.shape[1]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    logits = jnp.where(causal, logits, jnp.asarray(-jnp.inf, dtype))
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("hqk,hkd->hqd", p, v, precision=precision)


_heads_jit = jax.jit(_heads, static_argnames=("dtype", "precision"))


def attention(q, k, v, store=jnp.float32, dtype=jnp.float32,
              precision="highest", block_heads: int = 4):
    """Causal grouped attention, ``block_heads`` query heads at a time.
    The reference stores and computes in float32 at ``highest`` precision;
    a control rounds the inputs to a lower ``store`` type and computes
    every step in ``dtype`` at the default precision."""
    # rounded to ``store`` in programs of their own: inside one program
    # XLA may keep the excess precision and drop the rounding
    q, k, v = (jax.block_until_ready(x.astype(store)).astype(dtype)
               for x in (q, k, v))
    bh, bh_kv = q.shape[0], k.shape[0]
    group = bh // bh_kv
    outs = []
    for h0 in range(0, bh, block_heads):
        idx = jnp.arange(h0, min(h0 + block_heads, bh)) // group
        outs.append(_heads_jit(q[h0:h0 + block_heads], k[idx], v[idx],
                               dtype=dtype, precision=precision))
    return jnp.concatenate(outs).astype(jnp.float32)
