"""Jax-engine conformance: the jitted replay path against the numpy oracle.

Replay-from-log is **bit-identical** (see ``core.engine_jax``): given
identical told observations, ``SimulationRunner(engine="jax")`` commits
the same scores, traces, budget spends, and exhaustion points as the
numpy engine, observation for observation. Deterministic fixtures pin
the edge shapes (budget exhaustion mid-batch, inf failures, cache-miss
rows, single-row asks, revisit-only batches, empty caches) and a
hypothesis sweep drives random batches over one fixed space shape
(bounding jit recompiles to the padded power-of-two ladder).

Marked ``jax_engine``. The engine runs on whatever platform JAX
initialized (the CPU under ``JAX_PLATFORMS=cpu``); it never degrades to
numpy.
"""
import math
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _compat import given, settings, st
from _synth import parity_cache, total_charge

import repro.core.engine_jax as engine_jax
from repro.core.budget import Budget, BudgetExhausted
from repro.core.driver import SearchDriver, drive_many
from repro.core.methodology import evaluate_strategy, make_scorer
from repro.core.runner import SimulationRunner
from repro.core.space import RowBatch
from repro.core.strategies import get_strategy

pytestmark = pytest.mark.jax_engine

CACHE = parity_cache()
TOTAL = total_charge(CACHE)
# every strategy whose asks resolve through _run_rows, single-move shapes
# (simulated annealing, greedy ILS) included
STRATEGIES = ("random_search", "genetic_algorithm", "pso",
              "differential_evolution", "simulated_annealing", "greedy_ils")


def _observable(r: SimulationRunner):
    return (list(r.trace), r.fresh_evals, r.budget.spent_seconds,
            r.budget.spent_evals, sorted(r.memo))


def _runners(cache, **budget_kw):
    return (SimulationRunner(cache, Budget(**budget_kw), engine="numpy"),
            SimulationRunner(cache, Budget(**budget_kw), engine="jax"))


def _rows(cache, rows) -> RowBatch:
    """An index-native ask — the form whose resolution the jax engine
    owns (plain config lists take the keyed path on every engine)."""
    return RowBatch(cache.space.compiled, np.asarray(rows, dtype=np.int64))


# --------------------------------------------------------- replay-from-log
def test_whole_space_batch_bit_identical():
    """Full-space replay with revisits: every observation, trace entry,
    and budget float identical — and the jax runner actually dispatched."""
    n = CACHE.space.compiled.n_valid
    batch = _rows(CACHE, np.r_[np.arange(n), np.arange(n)])
    np_r, jx_r = _runners(CACHE, max_seconds=1e9)
    assert np_r.run_batch(batch) == jx_r.run_batch(batch)
    assert _observable(np_r) == _observable(jx_r)
    assert jx_r._jax_engine().dispatches > 0


def test_budget_exhaustion_mid_batch_matches():
    batch = _rows(CACHE, np.arange(CACHE.space.compiled.n_valid))
    np_r, jx_r = _runners(CACHE, max_seconds=TOTAL * 0.21)
    with pytest.raises(BudgetExhausted):
        np_r.run_batch(batch)
    with pytest.raises(BudgetExhausted):
        jx_r.run_batch(batch)
    assert _observable(np_r) == _observable(jx_r)


def test_eval_budget_exhaustion_matches():
    batch = _rows(CACHE, np.arange(CACHE.space.compiled.n_valid))
    np_r, jx_r = _runners(CACHE, max_evals=57)
    with pytest.raises(BudgetExhausted):
        np_r.run_batch(batch)
    with pytest.raises(BudgetExhausted):
        jx_r.run_batch(batch)
    assert _observable(np_r) == _observable(jx_r)
    assert jx_r.budget.spent_evals == 57


def test_inf_failures_flow_through_trace():
    """parity_cache plants inf-valued failures; they must commit (charged,
    traced as inf) identically on both engines."""
    batch = _rows(CACHE, np.arange(CACHE.space.compiled.n_valid))
    np_r, jx_r = _runners(CACHE, max_seconds=1e9)
    np_r.run_batch(batch)
    jx_r.run_batch(batch)
    assert _observable(np_r) == _observable(jx_r)
    infs = [t for t in jx_r.trace if math.isinf(t[1])]
    assert infs, "expected inf-valued failures in the fixture"


def test_cache_miss_rows_impute_mean_charge():
    cache = parity_cache(name="missy")
    for key in list(cache.results)[::5]:
        del cache.results[key]
    cache.invalidate_columns()
    batch = _rows(cache, np.arange(cache.space.compiled.n_valid))
    np_r, jx_r = _runners(cache, max_seconds=1e9)
    obs_n = np_r.run_batch(batch)
    obs_j = jx_r.run_batch(batch)
    assert obs_n == obs_j
    assert _observable(np_r) == _observable(jx_r)
    miss = [o for o in obs_j if o.status == "error" and not o.result.times_s
            and o.charge_s == cache.mean_eval_charge()]
    assert miss, "expected imputed misses"


def test_empty_cache_raises_same_clear_error():
    cache = parity_cache(name="empty")
    cache.results.clear()
    cache.invalidate_columns()
    batch = _rows(cache, np.arange(4))
    errors = {}
    for eng in ("numpy", "jax"):
        runner = SimulationRunner(cache, Budget(max_seconds=1e9), engine=eng)
        with pytest.raises(ValueError) as exc:
            runner.run_batch(batch)
        errors[eng] = str(exc.value)
    assert errors["numpy"] == errors["jax"]


def test_single_row_asks_dispatch_on_device():
    """Single-move shapes (simulated annealing et al.) must go through the
    device kernel too — uniform parity coverage, no silent host fallback."""
    np_r, jx_r = _runners(CACHE, max_seconds=1e9)
    for r in range(5):
        np_r.run_batch(_rows(CACHE, [r]))
        jx_r.run_batch(_rows(CACHE, [r]))
    np_r.run_batch(_rows(CACHE, [0]))  # revisit: memo gather, no dispatch
    jx_r.run_batch(_rows(CACHE, [0]))
    assert jx_r._jax_engine().dispatches == 5
    assert jx_r.fresh_evals == 5
    assert _observable(np_r) == _observable(jx_r)


@pytest.mark.parametrize("name", STRATEGIES)
@pytest.mark.parametrize(
    "budget_kw", [{"max_seconds": TOTAL * 0.3}, {"max_evals": 57},
                  {"max_seconds": TOTAL * 0.35, "max_evals": 57}],
    ids=["seconds", "evals", "both"])
def test_strategy_campaign_parity(name, budget_kw):
    """Whole campaigns (ask/tell through SearchDriver) are bit-identical
    across engines for every row-native strategy and budget kind."""
    runs = {}
    for eng in ("numpy", "jax"):
        runner = SimulationRunner(CACHE, Budget(**budget_kw), engine=eng)
        SearchDriver(get_strategy(name), CACHE.space, runner,
                     random.Random(1234)).run()
        runs[eng] = _observable(runner)
    assert runs["numpy"] == runs["jax"]


def test_drive_many_engine_jax_parity():
    def make(n=6):
        ds = []
        for i in range(n):
            runner = SimulationRunner(CACHE, Budget(max_seconds=TOTAL * 0.2))
            ds.append(SearchDriver(get_strategy("genetic_algorithm"),
                                   CACHE.space, runner, random.Random(100 + i)))
        return ds

    da, db = make(), make()
    drive_many(da)
    drive_many(db, engine="jax")
    for x, y in zip(da, db):
        assert _observable(x.runner) == _observable(y.runner)


def test_methodology_scores_bit_identical():
    reports = {
        eng: evaluate_strategy(lambda: get_strategy("genetic_algorithm"),
                               [make_scorer(CACHE, engine=eng)],
                               repeats=3, seed=3)
        for eng in ("vectorized", "jax")}
    assert reports["jax"].score == reports["vectorized"].score
    assert np.array_equal(reports["jax"].curve, reports["vectorized"].curve)
    assert reports["jax"].fresh_evals == reports["vectorized"].fresh_evals


def test_resume_mid_run_row_state_reseeds():
    """load_state_dict invalidates the row mirror; the jax engine must
    rebuild seen/obs_by_row from the restored memo, like the numpy path."""
    np_r, jx_r = _runners(CACHE, max_evals=48)
    np_r.run_batch(_rows(CACHE, np.arange(30)))
    snap = np_r.state_dict()
    jx_r.load_state_dict(snap)
    rest = _rows(CACHE, np.arange(10, 60))
    with pytest.raises(BudgetExhausted):
        np_r.run_batch(rest)
    with pytest.raises(BudgetExhausted):
        jx_r.run_batch(rest)
    assert _observable(np_r) == _observable(jx_r)


# ------------------------------------------------------------- replay_many
def test_replay_many_matches_runner_per_run():
    """The fused vmapped dispatch: each run's slice must equal what a
    SimulationRunner replaying the same fresh segment commits."""
    compiled = CACHE.space.compiled
    cols = CACHE.columns
    R, n = 8, compiled.n_valid
    rng = np.random.default_rng(7)
    rows = np.stack([rng.permutation(n) for _ in range(R)])
    max_s = TOTAL * 0.4
    accept, t_after, value, charge, spent, evals, exhausted = (
        np.asarray(o) for o in engine_jax.replay_many(
            cols, compiled, rows, max_seconds=max_s))
    for r in range(R):
        runner = SimulationRunner(CACHE, Budget(max_seconds=max_s))
        try:
            runner.run_batch(_rows(CACHE, rows[r]))
            assert not exhausted[r]
        except BudgetExhausted:
            assert exhausted[r]
        acc = accept[r]
        assert runner.budget.spent_seconds == spent[r]
        assert runner.budget.spent_evals == evals[r]
        trace_t = [t for t, _v, _c in runner.trace]
        trace_v = [v for _t, v, _c in runner.trace]
        assert trace_t == t_after[r][acc].tolist()
        assert trace_v == value[r][acc].tolist()


def test_replay_many_seen_basis_makes_revisits_free():
    compiled = CACHE.space.compiled
    cols = CACHE.columns
    seen = np.zeros(compiled.n_valid, dtype=bool)
    seen[::2] = True
    rows = np.arange(compiled.n_valid)[None, :]
    accept, _t, _v, _c, spent, evals, _x = (
        np.asarray(o) for o in engine_jax.replay_many(
            cols, compiled, rows, seen=seen))
    assert not accept[0][::2].any()
    assert accept[0][1::2].all()
    assert evals[0] == compiled.n_valid // 2


# ------------------------------------------------------ float64 on bits
def _f64_cases():
    rng = np.random.default_rng(11)
    n = 4096
    big = np.finfo(np.float64).max
    frac = rng.random(n)
    return {
        "lognormal": (rng.lognormal(-3, 3, n), rng.lognormal(-3, 3, n)),
        "all-exponents": (np.ldexp(rng.random(n), rng.integers(-1074, 1000, n)),
                          np.ldexp(rng.random(n), rng.integers(-1074, 1000, n))),
        "one-ulp": (frac, np.nextafter(frac, 2.0) - frac),
        "subnormal": (rng.random(n) * 1e-310, rng.random(n) * 1e-310),
        "overflow": (big * rng.random(n), big * rng.random(n)),
        "ties": (np.ldexp(rng.integers(1, 2 ** 53, n).astype(float), -52),
                 np.ldexp(rng.integers(1, 8, n).astype(float),
                          rng.integers(-60, -50, n))),
        "zero": (np.zeros(n), frac),
    }


@pytest.mark.parametrize("case", sorted(_f64_cases()))
def test_f64_add_bits_is_ieee_addition(case):
    """The device adds float64 in integer arithmetic on the bit patterns
    (the TPU has no float64 unit): every sum must be numpy's, bit for bit,
    round-half-even ties, subnormals and overflow to inf included."""
    from repro.core.engine_jax.replay import as_f64, f64_add_bits, f64_bits
    a, b = _f64_cases()[case]
    with jax.enable_x64(), np.errstate(over="ignore"):
        got = as_f64(jax.jit(f64_add_bits)(jnp.asarray(f64_bits(a)),
                                           jnp.asarray(f64_bits(b))))
        want = a + b
    assert np.array_equal(f64_bits(got), f64_bits(want))


# ------------------------------------------------------------------ tables
def test_tables_are_memoized_and_x64():
    compiled = CACHE.space.compiled
    cols = CACHE.columns
    rt = engine_jax.replay_tables(cols, compiled)
    assert engine_jax.replay_tables(cols, compiled) is rt
    # float64 columns travel as their exact bit patterns
    assert str(rt.time_s.dtype) == "int64"
    assert str(rt.charge_s.dtype) == "int64"
    assert np.array_equal(np.asarray(rt.time_s).view(np.float64),
                          cols.time_s, equal_nan=True)
    assert np.array_equal(np.asarray(rt.charge_s).view(np.float64),
                          cols.charge_s)
    assert str(rt.col_of_row.dtype) == "int32"


# ----------------------------------------------------- hypothesis sweeps
@given(st.integers(0, 10_000))
@settings(max_examples=20, deadline=None)
def test_property_random_batches_bit_identical(seed):
    """Random row batches (duplicates, revisits across calls, varying
    sizes) over the one fixed space shape: full observable parity. Batch
    sizes pad to the power-of-two ladder, so the sweep compiles a handful
    of kernel shapes, not one per example."""
    rng = np.random.default_rng(seed)
    n = CACHE.space.compiled.n_valid
    frac = 0.05 + (seed % 13) / 20.0
    budget_kw = ({"max_evals": 10 + seed % 120} if seed % 3 == 0
                 else {"max_seconds": TOTAL * frac})
    np_r, jx_r = _runners(CACHE, **budget_kw)
    for _ in range(3):
        size = int(rng.integers(1, 120))
        batch = _rows(CACHE, rng.integers(0, n, size))
        err = {}
        for tag, runner in (("numpy", np_r), ("jax", jx_r)):
            try:
                runner.run_batch(batch)
                err[tag] = False
            except BudgetExhausted:
                err[tag] = True
        assert err["numpy"] == err["jax"]
        assert _observable(np_r) == _observable(jx_r)
        if err["numpy"]:
            break
