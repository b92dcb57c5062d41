"""Mamba2 SSD (state-space duality) chunked scan — TPU Pallas kernel.

The attention-free hot spot of Mamba-2 and its hybrids (Nemotron-H,
Zamba2). Implements the SSD chunked algorithm (Dao & Gu, arXiv:2405.21060)
for every head h, whose B and C are those of its group g(h):

    h_t = exp(dt_t·a_h) · h_{t-1} + dt_t · B_{g,t} ⊗ x_t     (state update)
    y_t = C_{g,t} · h_t                                       (readout)

B and C are held per group, ``(G, L, N)``, as the published mixers hold
them (``n_groups``): head h reads group ``h // (BH / G)``, as
``flash_attention``'s query heads read their KV head. G = BH is the
ungrouped scan.

Chunked over the sequence: within a chunk of Q steps the output splits
into an *intra-chunk* quadratic term ((C Bᵀ) ∘ decay-mask) X — two MXU
matmuls — and an *inter-chunk* term C · (decay · h_in); the carried state
is updated with a third matmul. A grid step takes ``head_block`` heads of
one group: it fetches the group's chunk of B and C once and computes C·Bᵀ
(2Q²N, the largest term at N = 256) once for all of them. Then it loops
over its heads a few at a time (``_heads_per_pass``). The pass takes the
within-chunk prefix sums of its heads' dt·A in one masked sum, and runs
W·X one matmul per head, since W holds the head's own decay. The heads'
(N, P) states are held transposed and stacked, (k·P, N) in VMEM scratch,
so that C and B, which the heads share, are the MXU's stationary operand:
y_interᵀ = [h_1ᵀ; …; h_kᵀ]·Cᵀ and the state update [(x_1∘s_1)ᵀ; …]·B are
one matmul each for the pass. The chunk loop is the innermost
("arbitrary") grid dim — the TPU-native replacement for the paper's GPU
warp-level scan.

Tunables: the chunk length Q (``chunk``) and the heads a grid step takes
(``head_block``, dividing BH / G). Each pair is its own program; the
accumulator is always float32.
"""
from __future__ import annotations

import functools
import math
from typing import Mapping

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.costmodel import KernelWorkload, alignment_eff
from ..core.devices import DeviceModel
from ..core.searchspace import SearchSpace
from ..core.tunable import Constraint, tunables_from_dict

# Recording problem size (CPU interpret-mode live tuning): 4 heads sharing
# one group of B/C
SMOKE_PROBLEM = {"bh": 4, "bh_g": 1, "seq": 256, "p": 32, "n": 32}

# Nemotron-H-47B's Mamba-2 mixer at batch 1 and its 8192-token context:
# mamba_num_heads 256, n_groups 8, mamba_head_dim 64, ssm_state_size 256
DEFAULT_PROBLEM = {"bh": 256, "bh_g": 8, "seq": 8192, "p": 64, "n": 256}

_TILE = (8, 128)  # a float32 VMEM tile: (sublanes, lanes)
# float32 matmuls in float32 passes: at the chip's default precision the
# MXU rounds x, B, C and the state to bfloat16, and on a TPU v5e the scan's
# error (0.10 to 0.13 at |y| up to 29, seq 8192, N 256) came near that of
# a scan computed in bfloat16 throughout (0.17 to 0.23)
_HI = jax.lax.Precision.HIGHEST


def _heads_per_pass(head_block: int, p: int) -> int:
    """Heads one pass of the head loop takes: as many as stream 128 rows of
    their stacked states past each weight tile of Cᵀ and B that the MXU
    loads, ``128 / P`` (two at P = 64), or the largest divisor of
    ``head_block`` below that. On a TPU v5e, passes of up to 32 heads cut
    the 30 programs' call time by 4 % more and took 15 to 40 % more time
    to compile."""
    most = max(1, _TILE[1] // p)
    return max(d for d in range(1, min(head_block, most) + 1)
               if head_block % d == 0)


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, h_ref, *,
                chunk: int, heads: int):
    ci = pl.program_id(1)
    head_block, _, p = x_ref.shape

    @pl.when(ci == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    # the group's chunk of B and C, C·Bᵀ and Cᵀ, once for the block's heads
    b = b_ref[0].astype(jnp.float32)            # (Q, N)
    c = c_ref[0].astype(jnp.float32)            # (Q, N)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             precision=_HI,
                             preferred_element_type=jnp.float32)  # (Q, Q)
    ct = c.T                                    # (N, Q)
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lower = iota_i >= iota_j                    # step j at or before i
    last = jax.lax.broadcasted_iota(jnp.int32, (1, chunk, 1), 1) == chunk - 1

    def one_pass(s, carry):
        k = pl.ds(s * heads, heads)
        x = x_ref[k].astype(jnp.float32)        # (heads, Q, P)
        dt_row = dt_ref[k, pl.ds(ci, 1), :]     # (heads, 1, Q)
        # within-chunk prefix sums of the log decay dt·A of the pass's
        # heads: one masked sum on the VPU, exact in float32
        cum_col = jnp.sum(jnp.where(lower, dt_row * a_ref[k], 0.0), axis=2,
                          keepdims=True)        # (heads, Q, 1): cum_i
        cum_row = jnp.swapaxes(cum_col, 1, 2)   # (heads, 1, Q): cum_j
        total = jnp.sum(jnp.where(last, cum_col, 0.0), axis=1,
                        keepdims=True)          # (heads, 1, 1): cum_{Q-1}
        # intra-chunk, one matmul per head, since w holds the head's own
        # decay: w[i, j] = (C_i·B_j) exp(cum_i - cum_j) dt_j, j <= i
        w = cb * jnp.where(lower, jnp.exp(cum_col - cum_row), 0.0) * dt_row
        y_intra = jax.lax.dot_general(w, x, (((2,), (1,)), ((0,), (0,))),
                                      precision=_HI,
                                      preferred_element_type=jnp.float32)
        # inter-chunk, one matmul for the pass's heads, their transposed
        # states stacked: [h_1ᵀ; h_2ᵀ; ...] · Cᵀ; y_i gains exp(cum_i) C_i·h
        h_in = h_ref[s]                         # (heads·P, N)
        y_inter = jax.lax.dot(h_in, ct, precision=_HI,
                              preferred_element_type=jnp.float32)
        y_inter = jnp.swapaxes(y_inter.reshape(heads, p, chunk), 1, 2)
        y_ref[k] = (y_intra + jnp.exp(cum_col) * y_inter).astype(y_ref.dtype)
        # state, one matmul for the pass's heads: h_outᵀ = exp(total) h_inᵀ
        # + [(x_1 ∘ s_1)ᵀ; ...] · B, s_j = exp(total - cum_j) dt_j
        xs = jnp.swapaxes(x, 1, 2) * (jnp.exp(total - cum_row) * dt_row)
        bx = jax.lax.dot(xs.reshape(heads * p, chunk), b, precision=_HI,
                         preferred_element_type=jnp.float32)
        decay = jnp.broadcast_to(jnp.exp(total), (heads, p, 1))
        h_ref[s] = decay.reshape(heads * p, 1) * h_in + bx
        return carry

    jax.lax.fori_loop(0, head_block // heads, one_pass, 0)


def _padded(*shape: int) -> int:
    """Elements of a float32 array in VMEM, its last two dims padded to
    whole (8, 128) tiles."""
    *lead, rows, cols = (1,) * (2 - len(shape)) + shape
    count = -(-rows // _TILE[0]) * _TILE[0] * (-(-cols // _TILE[1]) * _TILE[1])
    for d in lead:
        count *= d
    return count


def vmem_bytes(chunk: int, head_block: int, seq: int, p: int, n: int) -> int:
    """VMEM one grid step holds: the double-buffered blocks of x, y, dt,
    A, B and C; the block's transposed states, ``(k·P, N)`` for each pass
    of the head loop over k heads; C·Bᵀ, Cᵀ and the masks; and the
    temporaries of one pass, four (k, Q, Q) and four (k, Q, P) with their
    transposes."""
    n_chunks = seq // chunk
    heads = _heads_per_pass(head_block, p)
    blocks = (2 * _padded(head_block, chunk, p)        # x and y
              + _padded(head_block, n_chunks, chunk)   # dt rows
              + _padded(head_block, 1, 1)              # A
              + 2 * _padded(chunk, n))                 # B and C
    states = _padded(head_block // heads, heads * p, n)
    temps = (3 * _padded(chunk, chunk) + _padded(n, chunk)
             + 4 * _padded(heads, chunk, chunk)
             + 4 * (_padded(heads, chunk, p) + _padded(heads, p, chunk)))
    return 4 * (2 * blocks + states + temps)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "head_block", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, *, chunk: int = 128, head_block: int = 1,
             interpret: bool = False) -> jax.Array:
    """SSD scan over a flattened (batch·heads) leading dim, B/C grouped.

    x: (BH, L, P); dt: (BH, L); a: (BH,); b/c: (G, L, N) with G dividing
    BH, head h reading group h // (BH / G). ``head_block`` divides BH / G.
    Returns y like x. ``dt`` and ``a`` are passed in blocks whose last two
    dimensions equal the array's, which the TPU's (8, 128) tiling rule
    accepts at any chunk.
    """
    bh, l, p = x.shape
    g, _, n = b.shape
    assert bh % g == 0 and (bh // g) % head_block == 0, (bh, g, head_block)
    assert l % chunk == 0
    n_chunks = l // chunk
    per_group = bh // g // head_block   # head blocks in one group
    heads_per_pass = _heads_per_pass(head_block, p)
    kernel = functools.partial(_ssd_kernel, chunk=chunk,
                               heads=heads_per_pass)
    heads = pl.BlockSpec((head_block, chunk, p), lambda i, j: (i, j, 0))
    group = pl.BlockSpec((1, chunk, n),
                         lambda i, j, r=per_group: (i // r, j, 0))
    # Mosaic's default scoped VMEM (16 MiB) is below what the larger
    # blocks hold; the limit asks for what they need
    limit = max(16 * 2**20, int(1.25 * vmem_bytes(chunk, head_block, l, p,
                                                  n)))
    return pl.pallas_call(
        kernel,
        grid=(bh // head_block, n_chunks),
        in_specs=[
            heads,
            pl.BlockSpec((head_block, n_chunks, chunk),
                         lambda i, j: (i, 0, 0)),
            pl.BlockSpec((head_block, 1, 1), lambda i, j: (i, 0, 0)),
            group,
            group,
        ],
        out_specs=heads,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM(
            (head_block // heads_per_pass, heads_per_pass * p, n),
            jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=limit),
        interpret=interpret,
        name="ssd_scan",
    )(x, dt.reshape(bh, n_chunks, chunk), a.reshape(bh, 1, 1), b, c)


# -------------------------------------------------------------------- ref
def ssd_ref(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
            c: jax.Array, **_unused) -> jax.Array:
    """Sequential oracle: literal recurrence, step by step, B/C grouped
    as ``ssd_scan`` takes them."""
    bh, l, p = x.shape
    g, _, n = b.shape
    b = jnp.repeat(b, bh // g, axis=0)
    c = jnp.repeat(c, bh // g, axis=0)

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
_DRAW = (256, 1024)  # the standard normals one step of ``_normal`` draws


@functools.partial(jax.jit, static_argnums=1)
def _normal(key: jax.Array, shape: tuple) -> jax.Array:
    """Standard normal float32 of ``shape``, in row-major order, drawn
    ``_DRAW`` at a time from the keys ``fold_in(key, i)``, i = 0, 1, ...
    The TPU compiler takes 16 to 34 s over one draw of a (256, 8192, 64)
    or (8, 8192, 256) array, and under a second over this loop; a cold
    recording compiles it anew."""
    size = math.prod(shape)
    steps = -(-size // math.prod(_DRAW))
    draws = jax.lax.map(
        lambda i: jax.random.normal(jax.random.fold_in(key, i), _DRAW,
                                    jnp.float32), jnp.arange(steps))
    return draws.reshape(-1)[:size].reshape(shape)


def live_inputs(seed: int, bh: int, bh_g: int, seq: int, p: int, n: int):
    """``(x, dt, a, b, c)`` from one key, split five ways in that order:
    x, B and C standard normal (``_normal``); dt uniform in [0.001, 0.1]
    (``time_step_min``, ``time_step_max``); A = -uniform[1, 16], Mamba-2's
    ``A_log`` initialisation. All float32."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = _normal(ks[0], (bh, seq, p))
    dt = jax.random.uniform(ks[1], (bh, seq), jnp.float32, 0.001, 0.1)
    a = -jax.random.uniform(ks[2], (bh,), jnp.float32, 1.0, 16.0)
    b = _normal(ks[3], (bh_g, seq, n))
    c = _normal(ks[4], (bh_g, seq, n))
    return x, dt, a, b, c


def make_live(problem: Mapping | None, interpret: bool):
    """Recorder callable: the grouped chunked scan on fixed inputs made
    from the problem's sizes and ``seed``."""
    p = {**SMOKE_PROBLEM, **(problem or {})}
    x, dt, a, b, c = live_inputs(p.get("seed", 9), p["bh"], p["bh_g"],
                                 p["seq"], p["p"], p["n"])

    def fn(conf: Mapping) -> None:
        out = ssd_scan(x, dt, a, b, c, chunk=conf["chunk"],
                       head_block=conf["head_block"], interpret=interpret)
        jax.block_until_ready(out)

    return fn


def space(seq: int = DEFAULT_PROBLEM["seq"], bh: int = DEFAULT_PROBLEM["bh"],
          bh_g: int = DEFAULT_PROBLEM["bh_g"]) -> SearchSpace:
    tunables = tunables_from_dict({
        "chunk": (32, 64, 128, 256, 512),
        "head_block": (1, 2, 4, 8, 16, 32),
    })
    constraints = (
        Constraint(lambda c: seq % c["chunk"] == 0, "chunk divides L"),
        Constraint(lambda c: (bh // bh_g) % c["head_block"] == 0,
                   "head_block divides BH/G"),
    )
    return SearchSpace(tunables, constraints, name="ssd")


def workload(bh: int = DEFAULT_PROBLEM["bh"],
             bh_g: int = DEFAULT_PROBLEM["bh_g"],
             seq: int = DEFAULT_PROBLEM["seq"], p: int = DEFAULT_PROBLEM["p"],
             n: int = DEFAULT_PROBLEM["n"]) -> KernelWorkload:
    def flops(c: Mapping) -> float:
        q, blocks = c["chunk"], bh // c["head_block"]
        per_head = 2 * q * q * p + 4 * q * n * p   # W·X, C·h, Bᵀ·X
        return (seq // q) * (bh * per_head + blocks * 2 * q * q * n)

    def hbm_bytes(c: Mapping, dev: DeviceModel) -> float:
        # float32: x read and y written once, dt once, B and C once per
        # head block
        blocks = bh // c["head_block"]
        return 4.0 * seq * (2 * bh * p + bh + 2 * blocks * n)

    def vmem(c: Mapping) -> float:
        return vmem_bytes(c["chunk"], c["head_block"], seq, p, n)

    def grid_size(c: Mapping) -> float:
        return bh // c["head_block"] * (seq // c["chunk"])

    def compute_eff(c: Mapping, dev: DeviceModel) -> float:
        q = c["chunk"]
        eff = alignment_eff(q, dev.mxu) * alignment_eff(n, dev.lane)
        eff *= min(1.0, q / dev.mxu) ** 0.5
        return 0.7 * eff  # cumsum/exp VPU work between matmuls

    return KernelWorkload("ssd", flops, hbm_bytes, vmem, grid_size,
                          compute_eff)
