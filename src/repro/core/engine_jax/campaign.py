"""Device-resident fused campaigns: whole tuning runs per XLA dispatch.

The ``campaign`` workload was stepping-bound: every generation of every
run paid one ask -> ``run_batch`` -> tell round-trip through the runner's
commit machinery, so a hyperparameter campaign (configs x spaces x repeats)
was ~10^4 host round-trips even though the jitted replay kernel resolves
millions of evaluations per dispatch. This module fuses the
budget-replay-commit leg of every concurrent run into a handful of vmapped
``_replay_vjit`` dispatches while keeping the bit-parity contract of the
per-ask replay engine.

The split that makes this possible: in simulation mode an observation's
*value* is a pure row lookup (``time_s[col_of_row[row]]``, inf for rows
outside the recorded set), and the array-native strategies (GA, PSO, DE,
random search) consume only ``observation.value`` in ``tell``. The ask/tell
trajectory is therefore *budget-independent* — the exact same numpy/python
RNG stream unfolds whether or not the budget would have stopped the run —
so the host can step the real strategy code as a **trajectory oracle**
against a precomputed value table (no Observation objects, no memo, no
budget), while the device performs the budget accounting (the
parity-critical left-to-right float64 ``lax.scan``) for *all* runs of a
campaign in one dispatch per segment. Everything the device rejects past
the exhaustion point is discarded, which is exactly what ``BudgetExhausted``
discards in the sequential loop: exhaustion is monotone (charges are
non-negative), so the committed prefix is identical.

Each dispatch is one host-device round trip. One ``device_put`` stages
the segment's inputs (rows as int32, since every space has far fewer
than 2**31 rows and ``col_of_row`` is int32 already; float64s as their
int64 bit patterns), and one ``device_get`` fetches the five outputs
only the device computes: the accept mask, the left-to-right spend
``t_after`` and each run's ``(spent, evals, exhausted)``. The program
still returns the 7-tuple of ``replay.py``; its ``value`` and ``charge``
stay on the device, because the host's ``value_of_row`` /
``charge_of_row`` are the same gathers of the same float64s
(``time_s`` / ``charge_s`` through ``col_of_row``; inf and the cache's
mean charge for a row outside the recorded set), so an accepted row's
value and charge taken from them are bit-identical.

Where draw counts are data-dependent (every strategy outside the allowlist,
bridge-adapted loops, empty caches whose imputed-miss error must surface on
the host), ``fuse_reason`` names the reason and the caller falls back to
the host drive — segmented host stepping remains the general path, the
device path is an eligibility-gated fast lane that commits bit-identical
state (tests/test_campaign_fused.py pins this against the numpy engine).
"""
from __future__ import annotations

import jax
import numpy as np
from jax import enable_x64

from ..cache import CachedResult
from ..runner import INVALID, Observation, SimulationRunner
from ..space import RowBatch
from ..spans import CAMPAIGN_BUILD, CAMPAIGN_STEP, REPLAY_DISPATCH, span
from .replay import _budget_limits, _pad_len, _replay_vjit, first_occurrence
from .tables import as_f64, f64_bits, replay_tables

# strategies whose ask/tell trajectory is host-replayable from values alone:
# tell reads only ``observation.value`` (never status/config/result), and
# retains no observation objects
FUSED_STRATEGIES = frozenset(
    {"random_search", "genetic_algorithm", "pso", "differential_evolution"})
# tell is a literal no-op: skip building the value feed entirely
_TELL_NOOP = frozenset({"random_search"})

# rows collected per run per segment before dispatching: large enough that
# budget-sized runs complete in one dispatch, small enough that a run whose
# budget exhausts early does not step its oracle far past the cutoff
SEGMENT_ROWS = 4096


class _ValueObs:
    """What the trajectory oracle tells the strategy: the minimal stand-in
    for an ``Observation`` (the fused strategies read only ``value``)."""

    __slots__ = ("value",)

    def __init__(self, value: float):
        self.value = value


def fuse_reason(driver) -> "str | None":
    """Why this driver cannot take the device-fused path (None = eligible).

    The reasons mirror the sequential semantics the fast lane must not
    change: bridge/legacy strategies have data-dependent ask streams, an
    empty cache must raise ``mean_eval_charge``'s error at the exact host
    point, and a GA/PSO/DE run with no budget cap never terminates — the
    sequential path at least surfaces progress while it spins.
    """
    strategy = driver.strategy
    name = getattr(strategy, "name", type(strategy).__name__)
    if name not in FUSED_STRATEGIES:
        return (f"strategy {name!r} is not array-native "
                f"(trajectory not host-replayable from values alone)")
    runner = driver.runner
    if not isinstance(runner, SimulationRunner):
        return f"runner {type(runner).__name__} is not a SimulationRunner"
    if not runner.columnar:
        return "runner is scalar (engine='scalar' is the parity reference)"
    if len(runner.cache.columns) == 0:
        return ("cache is empty: the imputed-miss charge error must "
                "surface on the host")
    budget = runner.budget
    if (budget.max_seconds is None and budget.max_evals is None
            and name != "random_search"):
        return f"unbounded budget: {name} never finishes without a cap"
    return None


class FusedRun:
    """One tuning run's fused execution state: the oracle's optimistic
    bookkeeping plus the device-committed prefix."""

    __slots__ = ("driver", "seen", "spent", "evals", "evals0", "max_s",
                 "max_e", "approx_s", "approx_e", "no_more_asks", "done",
                 "exhausted", "acc_rows", "acc_t", "acc_v", "acc_c")

    def __init__(self, driver):
        runner = driver.runner
        self.driver = driver
        # the oracle's own copy: marked optimistically at ask time, while
        # the runner's row state is only touched by the final commit
        self.seen = runner._row_state()[0].copy()
        budget = runner.budget
        self.spent = budget.spent_seconds   # device-authoritative after
        self.evals = budget.spent_evals     # each segment
        self.evals0 = budget.spent_evals
        self.max_s, self.max_e = _budget_limits(budget)
        # host stop heuristic only — np.add.reduce may differ from the
        # device's left-to-right sum by ULPs, so these never decide
        # exhaustion, only when to stop extending a segment
        self.approx_s = self.spent
        self.approx_e = self.evals
        self.no_more_asks = driver.state.finished
        self.done = driver.state.finished
        self.exhausted = False
        # committed (device-accepted) prefix, appended per segment
        self.acc_rows: list = []
        self.acc_t: list = []
        self.acc_v: list = []
        self.acc_c: list = []

    # ------------------------------------------------------------- results
    @property
    def fresh_evals(self) -> int:
        return self.evals - self.evals0

    def trace(self) -> list:
        """The run's fresh-commit trace as ``(t_cum, value, None)`` tuples
        — ``score_trace`` ignores the config column, so the scores-only
        path never materializes configs or Observations."""
        if not self.acc_rows:
            return []
        t = np.concatenate(self.acc_t).tolist()
        v = np.concatenate(self.acc_v).tolist()
        return [(ti, vi, None) for ti, vi in zip(t, v)]

    def improvements(self) -> tuple:
        """The run's improvement step function ``(times, bests)`` as
        float64 arrays — what ``SpaceScorer.score_improvements`` consumes.

        Bit-identical to scanning ``trace()`` with the sequential
        ``value < best`` loop: ``np.fmin.accumulate`` over the committed
        value column takes the same float64 minima in the same order, and
        an improvement is exactly a strictly-smaller running minimum
        (non-finite values never improve — ``inf < inf`` is False in both
        formulations). Lets scores-only consumers skip the Python trace
        entirely."""
        if not self.acc_rows:
            return (np.empty(0, dtype=np.float64),
                    np.empty(0, dtype=np.float64))
        t = np.concatenate(self.acc_t)
        v = np.concatenate(self.acc_v)
        run_min = np.fmin.accumulate(np.where(np.isfinite(v), v, np.inf))
        imp = np.empty(len(v), dtype=bool)
        imp[0] = np.isfinite(run_min[0])
        imp[1:] = run_min[1:] < run_min[:-1]
        return t[imp], run_min[imp]


def _collect_segment(run: FusedRun, value_of_row: np.ndarray,
                     charge_of_row: np.ndarray) -> tuple:
    """Step the run's trajectory oracle until the segment is full, the
    approximate budget is spent, or the strategy stops asking. Returns the
    flattened ``(rows, fresh)`` stream for the device."""
    driver = run.driver
    strategy, state = driver.strategy, driver.state
    feed_values = strategy.name not in _TELL_NOOP
    parts_r: list = []
    parts_f: list = []
    n = 0
    while not run.no_more_asks:
        batch = strategy.ask(state)
        if not batch:
            run.no_more_asks = True
            break
        if not isinstance(batch, RowBatch):  # pragma: no cover - guarded
            raise TypeError(
                f"{strategy.name} asked {type(batch).__name__}, not a "
                f"RowBatch; fuse_reason should have rejected it")
        rows = np.asarray(batch.rows, dtype=np.int64)
        # large duplicate-free asks (random search's permutation) skip the
        # argsort in first_occurrence: one O(n) bincount proves
        # distinctness; small generation-sized asks stay on the generic
        # path where the argsort is already cheap
        if len(rows) >= 1024 and np.bincount(rows).max(initial=0) <= 1:
            fresh = ~run.seen[rows]
        else:
            fresh = first_occurrence(rows) & ~run.seen[rows]
        run.seen[rows[fresh]] = True
        parts_r.append(rows)
        parts_f.append(fresh)
        n += len(rows)
        run.approx_s += float(np.add.reduce(charge_of_row[rows[fresh]]))
        run.approx_e += int(np.count_nonzero(fresh))
        if feed_values:
            values = value_of_row[rows].tolist()
            strategy.tell(state, [_ValueObs(v) for v in values])
        if (n >= SEGMENT_ROWS or run.approx_s >= run.max_s
                or run.approx_e >= run.max_e):
            break
    if not parts_r:
        return np.empty(0, dtype=np.int64), np.empty(0, dtype=bool)
    return np.concatenate(parts_r), np.concatenate(parts_f)


def _drive_group(runs: "list[FusedRun]", cols, compiled) -> None:
    """Drive one cache group's runs to completion in a handful of device
    dispatches (the whole point: not ~10^4), each inside one
    ``repro.replay.dispatch`` span and each one put and one fetch (see
    the module docstring)."""
    with span(CAMPAIGN_BUILD):
        tables = replay_tables(cols, compiled)
        col_map = cols.rows_for_space(compiled)
        safe = np.clip(col_map, 0, None)
        if tables.has_miss:
            # non-empty cache (fuse_reason gates empty ones), so this is
            # the same finite value every miss commit would compute lazily
            mean_charge = runs[0].driver.runner.cache.mean_eval_charge()
            value_of_row = np.where(col_map >= 0, cols.time_s[safe], np.inf)
            charge_of_row = np.where(col_map >= 0, cols.charge_s[safe],
                                     mean_charge)
        else:
            mean_charge = 0.0
            value_of_row = cols.time_s[safe]
            charge_of_row = cols.charge_s[safe]
        with enable_x64():
            mean_charge_d = jax.device_put(f64_bits(mean_charge))
    active = [r for r in runs if not r.done]
    while active:
        todo: list = []
        with span(CAMPAIGN_STEP):
            for run in active:
                rows, fresh = _collect_segment(run, value_of_row,
                                               charge_of_row)
                if len(rows) == 0:
                    run.done = True
                else:
                    todo.append((run, rows, fresh))
        if not todo:
            break
        with span(REPLAY_DISPATCH):
            # pad both axes to powers of two so the jit cache holds a handful
            # of (runs, length) shapes per space, not one per campaign round
            length = _pad_len(max(len(rows) for _run, rows, _f in todo))
            width = _pad_len(len(todo))
            rows_m = np.zeros((width, length), dtype=np.int32)
            fresh_m = np.zeros((width, length), dtype=bool)
            spent0 = np.zeros(width, dtype=np.float64)
            evals0 = np.zeros(width, dtype=np.int64)
            max_s = np.full(width, np.inf, dtype=np.float64)
            max_e = np.full(width, 2 ** 62, dtype=np.int64)
            for i, (run, rows, fresh) in enumerate(todo):
                rows_m[i, :len(rows)] = rows
                fresh_m[i, :len(fresh)] = fresh
                spent0[i] = run.spent
                evals0[i] = run.evals
                max_s[i] = run.max_s
                max_e[i] = run.max_e
            rows_d, fresh_d, spent0_d, evals0_d, max_s_d, max_e_d = (
                tables.device_put((rows_m, fresh_m, f64_bits(spent0), evals0,
                                   f64_bits(max_s), max_e)))
            with enable_x64():
                out = _replay_vjit(rows_d, fresh_d, tables.col_of_row,
                                   tables.time_s, tables.charge_s,
                                   mean_charge_d, spent0_d, evals0_d,
                                   max_s_d, max_e_d)
            # only what the device alone computes; value and charge are
            # the host's own gathers (value_of_row, charge_of_row)
            accept, t_after, spent, evals, exhausted = tables.device_get(
                (out[0], out[1], out[4], out[5], out[6]))
            # float64 columns come back as bit patterns (see replay.py)
            t_after = as_f64(t_after)
            spent = as_f64(spent)
            survivors: list = []
            for i, (run, rows, _fresh) in enumerate(todo):
                n = len(rows)
                acc = np.nonzero(accept[i, :n])[0]
                if len(acc):
                    acc_rows = rows[acc]
                    run.acc_rows.append(acc_rows)
                    run.acc_t.append(t_after[i, acc])
                    run.acc_v.append(value_of_row[acc_rows])
                    run.acc_c.append(charge_of_row[acc_rows])
                # chained-scan seed: the device's final (spent, evals) feeds
                # the next segment, so the left-to-right addition sequence is
                # one unbroken chain — bit-identical to a single long scan
                run.spent = float(spent[i])
                run.evals = int(evals[i])
                run.approx_s = run.spent
                run.approx_e = run.evals
                if exhausted[i]:
                    run.exhausted = True
                    run.done = True
                elif run.no_more_asks:
                    run.done = True
                else:
                    survivors.append(run)
            active = survivors


def _commit_run(run: FusedRun) -> None:
    """Materialize the device-accepted prefix into the runner — memo,
    trace, budget, freshness — exactly as the sequential commit paths do
    (mirrors ``ReplayEngine.commit_rows``'s host-side commit), then finish
    the driver the way ``drive_many`` would."""
    driver = run.driver
    runner = driver.runner
    seen, obs_by_row, _col_arr, col_list, cols = runner._row_state()
    if run.acc_rows:
        rows = np.concatenate(run.acc_rows)
        t_col = np.concatenate(run.acc_t).tolist()
        vals = np.concatenate(run.acc_v).tolist()
        chgs = np.concatenate(run.acc_c).tolist()
        seen[rows] = True
        cs = runner.space.compiled
        cfg_tab, id_tab = cs.configs, cs.ids
        rows_l = rows.tolist()
        cfgs = [cfg_tab[r] for r in rows_l]
        records = cols.records
        new_obs = Observation.__new__
        set_dict = object.__setattr__
        memo = runner.memo
        for r, cfg, val, chg in zip(rows_l, cfgs, vals, chgs):
            col = col_list[r]
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
        runner.trace.extend(zip(t_col, vals, cfgs))
        runner.fresh_evals += len(rows_l)
        runner._rows_memo_len = len(memo)
    budget = runner.budget
    budget.spent_seconds = run.spent
    budget.spent_evals = run.evals
    state = driver.state
    state.finished = True
    driver.exhausted = run.exhausted
    state.close()


def drive_fused(drivers, materialize: bool = True) -> "list[FusedRun]":
    """Drive every driver's campaign through the device-fused path.

    All drivers must be eligible (``fuse_reason(d) is None`` — callers
    partition first; this raises ``ValueError`` otherwise). Runs are
    grouped by (cache columns, compiled space) identity and each group
    resolves as a few vmapped dispatches. With ``materialize=True``
    (the ``drive_many`` contract) each runner's observable state — memo,
    trace, budget, ``fresh_evals`` — commits bit-identically to the
    sequential engines; ``materialize=False`` skips Observation/memo
    construction for scores-only callers (the methodology reads
    ``FusedRun.trace()``/``fresh_evals``/``spent`` instead).
    """
    runs: list[FusedRun] = []
    groups: dict = {}
    with span(CAMPAIGN_BUILD):
        for d in drivers:
            reason = fuse_reason(d)
            if reason is not None:
                raise ValueError(
                    f"driver is not device-fusable: {reason} "
                    f"(partition with fuse_reason first)")
            run = FusedRun(d)
            runs.append(run)
            runner = d.runner
            key = (id(runner.cache.columns), id(runner.space.compiled))
            groups.setdefault(key, (runner.cache.columns,
                                    runner.space.compiled, []))[2].append(run)
    for cols, compiled, group in groups.values():
        _drive_group(group, cols, compiled)
    if materialize:
        for run in runs:
            _commit_run(run)
    else:
        for run in runs:
            run.driver.state.finished = True
            run.driver.exhausted = run.exhausted
            run.driver.state.close()
    return runs
