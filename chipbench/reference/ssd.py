"""Plain reference for the Mamba-2 state-space-duality scan with grouped B
and C, written from the recurrence; it imports nothing of the program
under test.

For each head h of group g(h) = h // (heads / groups), step by step:

    h_t = exp(dt_t a_h) h_{t-1} + dt_t B_{g(h),t} (outer) x_t
    y_t = C_{g(h),t} . h_t

with h_0 = 0, in float32 under ``jax.default_matmul_precision("highest")``.
Computed one group of heads at a time, so that the (heads, N, P) state
and the outputs fit beside the program's.

Departures from the published Mamba-2 mixer: the scan alone. The input
projection, the causal conv1d, the D skip connection, the z gate, the
gated RMS norm and the output projection are left out, as the attention
cell leaves out the QKV projections; dt arrives after its softplus and
clamp, A after -exp(A_log).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


DRAW = (256, 1024)


@functools.partial(jax.jit, static_argnums=1)
def _normal(key, shape):
    """Standard normal float32 of ``shape`` in row-major order: block i of
    ``DRAW`` values is ``jax.random.normal(fold_in(key, i), DRAW)``."""
    size = math.prod(shape)
    steps = -(-size // math.prod(DRAW))
    draws = jax.lax.map(
        lambda i: jax.random.normal(jax.random.fold_in(key, i), DRAW,
                                    jnp.float32), jnp.arange(steps))
    return draws.reshape(-1)[:size].reshape(shape)


def inputs(key_seed: int, bh: int, bh_g: int, seq: int, p: int, n: int):
    """The cell's x, dt, A, B, C from one key, split five ways in that
    order: x (bh, seq, p), B and C (bh_g, seq, n) standard normal
    (``_normal``); dt (bh, seq) uniform in [0.001, 0.1], the published
    ``time_step_min`` and ``time_step_max``; A (bh,) = -uniform[1, 16],
    Mamba-2's ``A_log`` initialisation. All float32."""
    ks = jax.random.split(jax.random.PRNGKey(key_seed), 5)
    x = _normal(ks[0], (bh, seq, p))
    dt = jax.random.uniform(ks[1], (bh, seq), jnp.float32, 0.001, 0.1)
    a = -jax.random.uniform(ks[2], (bh,), jnp.float32, 1.0, 16.0)
    b = _normal(ks[3], (bh_g, seq, n))
    c = _normal(ks[4], (bh_g, seq, n))
    return x, dt, a, b, c


def _group(x, dt, a, b, c, dtype, precision):
    """The recurrence for the heads of one group: x (H, L, P), dt (H, L),
    a (H,), b and c (L, N)."""
    heads, _, p = x.shape
    n = b.shape[-1]

    def step(h, t):
        x_t, dt_t, b_t, c_t = t            # (H, P), (H,), (N,), (N,)
        decay = jnp.exp(dt_t * a)          # (H,)
        h = (decay[:, None, None] * h
             + dt_t[:, None, None] * b_t[None, :, None] * x_t[:, None, :])
        return h, jnp.einsum("hnp,n->hp", h, c_t, precision=precision)

    h0 = jnp.zeros((heads, n, p), dtype)
    _, ys = jax.lax.scan(step, h0, (x.transpose(1, 0, 2), dt.T, b, c))
    return ys.transpose(1, 0, 2)


_group_jit = jax.jit(_group, static_argnames=("dtype", "precision"))


def scan(x, dt, a, b, c, store=jnp.float32, dtype=jnp.float32,
         precision="highest"):
    """The grouped scan, one group of heads at a time. The reference
    stores and computes in float32 at ``highest`` precision; a control
    rounds the inputs to a lower ``store`` type and computes every step
    in ``dtype`` at the default precision."""
    # rounded to ``store`` in programs of their own: inside one program
    # XLA may keep the excess precision and drop the rounding
    x, dt, a, b, c = (jax.block_until_ready(v.astype(store)).astype(dtype)
                      for v in (x, dt, a, b, c))
    heads = x.shape[0] // b.shape[0]
    outs = []
    with jax.default_matmul_precision(precision or "default"):
        for g in range(b.shape[0]):
            hs = slice(g * heads, (g + 1) * heads)
            outs.append(_group_jit(x[hs], dt[hs], a[hs], b[g], c[g],
                                   dtype=dtype, precision=precision))
    return jnp.concatenate(outs).astype(jnp.float32)
