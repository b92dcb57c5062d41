"""The jitted replay-from-log path: bit-identical to the numpy engine.

The kernel resolves one batch of space rows against the device tables:
value/charge gathers, then a ``lax.scan`` that accumulates the budget with
the exact left-to-right float64 additions of the scalar loop and
``np.cumsum`` (any parallel scan — ``jnp.cumsum`` included — reassociates
the sums and drifts by ULPs, which the parity suite would catch). The
scan's carry is deliberately minimal: ``(spent, evals)`` only. A rejected
fresh evaluation implies ``spent``/``evals`` already reached the cap, and
charges are non-negative, so exhaustion is monotone — the per-step
``stopped`` flag of a naive transcription is redundant, and dropping it
from the carry is worth ~15x on the CPU backend.

Every float64 quantity lives on the device as its IEEE-754 bit pattern in
an int64, and the additions are done in integer arithmetic
(``f64_add_bits``). The TPU has no float64 unit: XLA splits a float64 into
a pair of float32 (about 48 significant bits), so a native float64 scan
there would round differently from the host. Integer arithmetic is exact
on every backend; the host reinterprets the bits (``as_f64``).

Within-batch first-occurrence dedup stays on the host (the same stable
argsort as ``SimulationRunner._commit_rows_vectorized``): a device
scatter-min over the whole batch costs more than the entire scan, and the
host mask is one cheap bool input. ``fresh`` therefore arrives fully
resolved (first occurrence x not-yet-seen), and the kernel only applies the
budget to it.

Batches are padded to power-of-two lengths so the jit cache holds a handful
of shapes per space instead of one per ask size.

A dispatch moves its data in two transfer calls of its ``ReplayTables``:
one ``device_put`` of every per-call input and one ``device_get`` of the
outputs the host reads, whose copies all start before any is waited on.
The programs return the 7-tuple ``(accept, t_after, value, charge, spent,
evals, exhausted)``; ``ReplayEngine`` and ``replay_many`` fetch all seven,
the fused campaign only the five the device alone computes (campaign.py).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64

from ..budget import BudgetExhausted
from ..cache import CachedResult
from ..runner import Observation
from ..spans import REPLAY_DISPATCH, span
from .tables import ReplayTables, as_f64, f64_bits, replay_tables

INVALID = float("inf")
_PAD_MIN = 8
# scan unroll: amortizes XLA's per-iteration loop overhead on CPU; measured
# best around 8 (4 is within noise, 16+ regresses from code bloat)
_UNROLL = 8
# unlimited-budget stand-ins (device scalars cannot be None)
_NO_MAX_S = float("inf")
_NO_MAX_E = 2 ** 62
_F64_ONE = 1 << 52          # the implicit leading bit of a binary64 significand
_F64_INF = 0x7FF0 << 48     # bit pattern of +inf


def _to_host(tables: ReplayTables, out) -> tuple:
    """A replay dispatch's outputs ``(accept, t_after, value, charge,
    spent, evals, exhausted)`` as host arrays, fetched in one transfer
    call, the float64 columns reinterpreted from their bit patterns."""
    accept, t_after, value, charge, spent, evals, exhausted = (
        tables.device_get(tuple(out)))
    return (accept, as_f64(t_after), as_f64(value), as_f64(charge),
            as_f64(spent), evals, exhausted)


def f64_add_bits(a, b):
    """``a + b`` in IEEE-754 binary64, round half to even, computed on the
    int64 bit patterns of two non-negative finite doubles (sums past the
    largest double saturate to +inf). Non-negative doubles order like their
    bit patterns, so the caller compares them as integers too.

    Operands are aligned with three extra bits (guard, round, sticky),
    added, renormalized by at most one place, and rounded. Subnormals need
    no special case: their exponent field is 0 but they scale like
    exponent 1, and a significand that carries into bit 52 re-encodes as
    the next exponent by plain integer addition."""
    hi = jnp.maximum(a, b)
    lo = jnp.minimum(a, b)
    e_hi = hi >> 52
    e_lo = lo >> 52
    m_hi = ((hi & (_F64_ONE - 1)) | jnp.where(e_hi > 0, _F64_ONE, 0)) << 3
    m_lo = ((lo & (_F64_ONE - 1)) | jnp.where(e_lo > 0, _F64_ONE, 0)) << 3
    e_hi = jnp.maximum(e_hi, 1)
    # m_lo < 2**56: a shift of 60 already clears it into the sticky bit
    shift = jnp.minimum(e_hi - jnp.maximum(e_lo, 1), 60)
    sticky = (m_lo & ((jnp.int64(1) << shift) - 1)) != 0
    s = m_hi + ((m_lo >> shift) | sticky.astype(jnp.int64))
    carry = s >> 56                       # 0 or 1: the sum grew a bit
    s = (s >> carry) | (s & carry)        # the bit shifted out stays sticky
    grs = s & 7
    m = s >> 3
    up = (grs > 4) | ((grs == 4) & ((m & 1) == 1))
    bits = ((e_hi + carry - 1) << 52) + m + up.astype(jnp.int64)
    return jnp.minimum(bits, _F64_INF)


def _pad_len(n: int) -> int:
    return max(_PAD_MIN, 1 << max(0, int(n - 1).bit_length()))


def first_occurrence(rows: np.ndarray) -> np.ndarray:
    """Host-side within-batch dedup mask — the exact stable-argsort
    first-occurrence computation of ``_commit_rows_vectorized``."""
    n = len(rows)
    order = np.argsort(rows, kind="stable")
    sorted_rows = rows[order]
    first_sorted = np.empty(n, dtype=bool)
    first_sorted[:1] = True
    first_sorted[1:] = sorted_rows[1:] != sorted_rows[:-1]
    first = np.empty(n, dtype=bool)
    first[order] = first_sorted
    return first


def budget_scan(fresh, charge, spent0, evals0, max_s, max_e):
    """Sequential budget accounting over one batch segment.

    Bit-for-bit the scalar commit loop: a fresh evaluation commits iff
    ``spent < max_s and evals < max_e`` *before* the eval; committed
    charges accumulate left-to-right in float64. ``charge``, ``spent0``
    and ``max_s`` are float64 bit patterns (``f64_bits``). Returns the
    accept mask, the after-commit spend bits per entry (the trace time
    column), the final ``(spent, evals)``, and whether any fresh
    evaluation was rejected (the ``BudgetExhausted`` point of the
    equivalent ``run`` loop)."""

    def body(carry, x):
        spent, evals = carry
        f, c = x
        commit = f & (spent < max_s) & (evals < max_e)
        spent2 = jnp.where(commit, f64_add_bits(spent, c), spent)
        return (spent2, evals + commit.astype(evals.dtype)), (commit, spent2)

    (spent, evals), (accept, t_after) = jax.lax.scan(
        body, (spent0, evals0), (fresh, charge), unroll=_UNROLL)
    exhausted = jnp.any(fresh & ~accept)
    return accept, t_after, spent, evals, exhausted


def _replay_segment(rows, fresh, col_of_row, time_s, charge_s, mean_charge,
                    spent0, evals0, max_s, max_e):
    """One run's segment commit: gathers + ``budget_scan``, every float64
    as its bit pattern. Rows absent from the recorded set (col < 0) take
    the imputed-miss path — value inf, mean charge — like the keyed/scalar
    engines."""
    col = col_of_row[rows]
    miss = col < 0
    safe = jnp.clip(col, 0)
    value = jnp.where(miss, _F64_INF, time_s[safe])
    charge = jnp.where(miss, mean_charge, charge_s[safe])
    accept, t_after, spent, evals, exhausted = budget_scan(
        fresh, charge, spent0, evals0, max_s, max_e)
    return accept, t_after, value, charge, spent, evals, exhausted


_replay_jit = jax.jit(_replay_segment)
# fused multi-run variant: tables are shared, per-run rows/fresh/budget;
# one dispatch resolves every concurrent run's segment
_replay_vjit = jax.jit(jax.vmap(
    _replay_segment, in_axes=(0, 0, None, None, None, None, 0, 0, 0, 0)))


def _budget_limits(budget) -> tuple:
    max_s = _NO_MAX_S if budget.max_seconds is None else float(budget.max_seconds)
    max_e = _NO_MAX_E if budget.max_evals is None else int(budget.max_evals)
    return max_s, max_e


class ReplayEngine:
    """Row-batch resolution for one ``SimulationRunner`` on the jax device.

    The host stays the source of truth: observations, memo, trace, and
    budget commit exactly as ``_commit_rows_vectorized`` does, from arrays
    the kernel computed. Every batch containing a fresh row dispatches —
    including single-row asks — so the conformance suite exercises the
    device path at every shape; fully-memoized batches short-circuit to the
    same pure host gather as the numpy path (no engine semantics involved).
    """

    def __init__(self, runner):
        self.runner = runner
        self.dispatches = 0  # device kernel launches (conformance hook)

    def commit_rows(self, rows) -> "list | BudgetExhausted":
        runner = self.runner
        rows = np.asarray(rows, dtype=np.int64)
        n = len(rows)
        seen, obs_by_row, col_of_row, _col_list, cols = runner._row_state()
        if len(cols) == 0:
            # empty cache: every row is an imputed miss and
            # mean_eval_charge's clear error must surface at the exact
            # point the scalar path raises it — keep that on the host
            return runner._commit_rows_loop(rows)
        seen_rows = seen[rows]
        if seen_rows.all():
            # revisit-only batch: pure memo gather, nothing to account
            return [obs_by_row[r] for r in rows.tolist()]
        fresh = first_occurrence(rows) & ~seen_rows
        col_rows = col_of_row[rows]
        mean_charge = (runner.cache.mean_eval_charge()
                       if (col_rows[fresh] < 0).any() else 0.0)
        budget = runner.budget
        max_s, max_e = _budget_limits(budget)
        self.dispatches += 1
        with span(REPLAY_DISPATCH):
            npad = _pad_len(n)
            rows_p = np.zeros(npad, dtype=np.int64)
            rows_p[:n] = rows
            fresh_p = np.zeros(npad, dtype=bool)
            fresh_p[:n] = fresh
            tables = replay_tables(cols, runner.space.compiled)
            (rows_d, fresh_d, mean_charge_d, spent0_d, evals0_d, max_s_d,
             max_e_d) = tables.device_put(
                (rows_p, fresh_p, f64_bits(mean_charge),
                 f64_bits(budget.spent_seconds),
                 np.int64(budget.spent_evals), f64_bits(max_s),
                 np.int64(max_e)))
            with enable_x64():
                out = _replay_jit(rows_d, fresh_d, tables.col_of_row,
                                  tables.time_s, tables.charge_s,
                                  mean_charge_d, spent0_d, evals0_d,
                                  max_s_d, max_e_d)
            accept, t_after, value, charge, spent, evals, exhausted = (
                _to_host(tables, out))
        # ------------------------------------------------- host-side commit
        # (mirrors _commit_rows_vectorized: fresh commits build
        # Observations, revisits gather from the row-indexed object array)
        acc_idx = np.nonzero(accept[:n])[0]
        cut = len(acc_idx)
        if cut:
            acc_rows = rows[acc_idx]
            acc_cols = col_rows[acc_idx]
            seen[acc_rows] = True
            vals = value[acc_idx].tolist()
            chgs = charge[acc_idx].tolist()
            cs = runner.space.compiled
            cfg_tab, id_tab = cs.configs, cs.ids
            cfgs_acc = [cfg_tab[r] for r in acc_rows.tolist()]
            records = cols.records
            new_obs = Observation.__new__
            set_dict = object.__setattr__
            memo = runner.memo
            for r, col, cfg, val, chg in zip(acc_rows.tolist(),
                                             acc_cols.tolist(),
                                             cfgs_acc, vals, chgs):
                if col >= 0:
                    rec = records[col]
                    status = rec.status
                else:
                    rec = CachedResult("error", INVALID, (), chg)
                    status = "error"
                obs = new_obs(Observation)
                set_dict(obs, "__dict__",
                         {"config": cfg, "value": val, "status": status,
                          "charge_s": chg, "result": rec})
                obs_by_row[r] = obs
                memo[id_tab[r]] = obs
            runner.trace.extend(zip(t_after[acc_idx].tolist(), vals,
                                    cfgs_acc))
            budget.spent_seconds = float(spent)
            budget.spent_evals = int(evals)
            runner.fresh_evals += cut
            runner._rows_memo_len = len(memo)
        if exhausted:
            try:
                budget.check()  # same exception/message as the scalar path
            except BudgetExhausted as exc:
                return exc
        return [obs_by_row[r] for r in rows.tolist()]


def replay_many(cols, compiled, rows_matrix, *, seen=None,
                spent0=None, evals0=None, max_seconds=None, max_evals=None,
                mean_charge: float = 0.0,
                tables: "ReplayTables | None" = None):
    """Fused fresh-replay: resolve R concurrent runs' row segments in one
    vmapped dispatch (the workload behind the ``jax_replay`` bench).

    ``rows_matrix`` is (R, N) int rows; per-run scalars broadcast from
    Python numbers or arrive as (R,) arrays. Returns host arrays
    ``(accept, t_after, value, charge, spent, evals, exhausted)`` — each
    run's slice bit-identical to what a ``SimulationRunner`` replaying the
    same segment would commit (tests/test_engine_jax.py pins this). Rows
    must be within-run unique (fresh replay) unless a precomputed ``seen``
    basis makes duplicates revisits; for general logs use ``ReplayEngine``.
    """
    if tables is None:
        tables = replay_tables(cols, compiled)
    rows_matrix = np.asarray(rows_matrix, dtype=np.int64)
    runs, _n = rows_matrix.shape
    if seen is None:
        fresh = np.ones(rows_matrix.shape, dtype=bool)
    else:
        seen = np.asarray(seen)
        fresh = ~seen[rows_matrix] if seen.ndim == 1 \
            else ~np.take_along_axis(seen, rows_matrix, axis=1)

    def per_run(x, default, dtype):
        if x is None:
            x = default
        arr = (f64_bits(x) if dtype == np.float64
               else np.asarray(x, dtype=dtype))
        return np.broadcast_to(arr, (runs,))

    rows_d, fresh_d, mean_charge_d, *budgets = tables.device_put(
        (rows_matrix, fresh, f64_bits(mean_charge),
         per_run(spent0, 0.0, np.float64), per_run(evals0, 0, np.int64),
         per_run(max_seconds, _NO_MAX_S, np.float64),
         per_run(max_evals, _NO_MAX_E, np.int64)))
    with enable_x64():
        out = _replay_vjit(rows_d, fresh_d, tables.col_of_row, tables.time_s,
                           tables.charge_s, mean_charge_d, *budgets)
    return _to_host(tables, out)
