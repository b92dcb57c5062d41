"""Device-resident fused campaigns against the scalar/numpy oracle.

The fused executor (``core.engine_jax.campaign``) drives whole tuning
runs — ask → budget-replay-commit → tell — through vmapped jitted
dispatches while a host trajectory oracle steps the real strategy code.
Its contract is the strong one: committed runner state is **bit-identical**
to driving each run alone on the numpy engine, including budget floats,
exhaustion points, and trace order. These tests pin that contract over

  * a deterministic (strategy × hyperparameter × budget × seed) grid,
    with budgets chosen to exhaust mid-generation and mid-batch;
  * a hypothesis sweep over budgets/seeds (same fixed space shape, so
    jit recompiles stay on the padded power-of-two ladder);
  * the scores-only path (``materialize=False`` + ``improvements()``),
    which must reproduce the sequential improvement scan bit-for-bit;
  * suspend/resume: snapshots taken around a fused drive pickle cleanly
    (no device arrays) and resume into either engine;
  * the fallback protocol: ineligible strategies degrade with a one-time
    ``FuseFallbackNotice`` naming the strategy and reason, and the chosen
    mode is surfaced on drivers and ``AggregateReport.fuse``.
  * the eligibility gate: each reason ``fuse_reason`` turns a run away,
    and the one unbounded run it admits (random search), which fused
    covers the space exactly.

Budgets here always stay below the cache's total fresh charge: an
over-provisioned budget can never finish a revisit-heavy population loop
(zero-charge revisits make no progress), identically in both engines.
"""
import math
import pickle
import random
import warnings

import numpy as np
import pytest
from _compat import given, settings, st
from _synth import parity_cache, total_charge

import repro.core.engine_jax as engine_jax
from repro.core import driver as driver_mod
from repro.core.budget import Budget
from repro.core.cache import CacheFile
from repro.core.devices import V5E
from repro.core.driver import FuseFallbackNotice, SearchDriver, drive_many
from repro.core.methodology import evaluate_strategy, make_scorer
from repro.core.runner import CostModelRunner, SimulationRunner
from repro.core.strategies import get_strategy
from repro.kernels import gemm

pytestmark = pytest.mark.jax_engine

CACHE = parity_cache()
TOTAL = total_charge(CACHE)
N_VALID = CACHE.space.compiled.n_valid


def _missy_cache():
    """The parity space with every fifth result unrecorded: those rows
    take the imputed-miss path (value inf, the cache's mean charge)."""
    cache = parity_cache(name="missy")
    for key in list(cache.results)[::5]:
        del cache.results[key]
    cache.invalidate_columns()
    return cache


MISSY = _missy_cache()

# (strategy, hyperparams, budget kwargs): mid-generation eval exhaustion,
# mid-batch time exhaustion, and a natural finish (random_search is the
# only fused strategy that stops asking on its own)
CASES = [
    ("random_search", {}, {"max_seconds": 1e9}),
    ("random_search", {}, {"max_evals": 37}),
    ("genetic_algorithm",
     {"popsize": 20, "maxiter": 100, "method": "uniform",
      "mutation_chance": 10}, {"max_seconds": TOTAL * 0.4}),
    ("genetic_algorithm",
     {"popsize": 30, "maxiter": 50, "method": "two_point",
      "mutation_chance": 20}, {"max_evals": 137}),
    ("pso", {"popsize": 20, "maxiter": 100, "c1": 2.0, "c2": 1.0},
     {"max_seconds": TOTAL * 0.3}),
    ("pso", {"popsize": 30, "maxiter": 50, "c1": 1.0, "c2": 0.5},
     {"max_seconds": TOTAL * 0.25, "max_evals": 100}),
    ("differential_evolution", {}, {"max_seconds": TOTAL * 0.2}),
]


@pytest.fixture(autouse=True)
def _fresh_notice_latch():
    """The fallback notice fires once per (strategy, reason) per process;
    reset so each test observes its own warnings."""
    saved = set(driver_mod._fuse_noticed)
    driver_mod._fuse_noticed.clear()
    yield
    driver_mod._fuse_noticed.clear()
    driver_mod._fuse_noticed.update(saved)


def _observable(r: SimulationRunner):
    return (list(r.trace), r.fresh_evals, r.budget.spent_seconds,
            r.budget.spent_evals, sorted(r.memo))


def _committed(r: SimulationRunner):
    """Each committed observation's value, charge and status."""
    return {k: (o.value, o.charge_s, o.status) for k, o in r.memo.items()}


def _driver(name, hp, seed, budget_kw, engine, cache=CACHE):
    runner = SimulationRunner(cache, Budget(**budget_kw), engine=engine)
    return SearchDriver(get_strategy(name, **hp), cache.space, runner,
                        random.Random(seed))


def _improvements_scan(trace):
    """Sequential reference: strict running-minimum improvements."""
    ts, bs, best = [], [], math.inf
    for t, v, _cfg in trace:
        if v < best:
            best = v
            ts.append(t)
            bs.append(v)
    return np.asarray(ts, dtype=np.float64), np.asarray(bs, dtype=np.float64)


# ----------------------------------------------------------- bit-parity
@pytest.mark.parametrize("seed,cache", [
    (0, CACHE), (1, CACHE), (2, CACHE),
    # imputed misses over short segments: the host's value and charge of
    # a miss row (inf, the mean charge) against the device's, over many
    # dispatches a run
    (0, MISSY),
], ids=["0", "1", "2", "missy"])
def test_drive_many_device_bit_identical(seed, cache, monkeypatch):
    """fuse="device" commits the same observable runner state as the
    numpy oracle, case by case, and records the chosen mode."""
    calls = []
    if cache is MISSY:
        replay = engine_jax.campaign._replay_vjit

        def counted(*args):
            calls.append(1)
            return replay(*args)

        monkeypatch.setattr(engine_jax.campaign, "_replay_vjit", counted)
        monkeypatch.setattr(engine_jax.campaign, "SEGMENT_ROWS", 40)
    ref = [_driver(n, hp, seed + i, bk, "numpy", cache)
           for i, (n, hp, bk) in enumerate(CASES)]
    dev = [_driver(n, hp, seed + i, bk, "jax", cache)
           for i, (n, hp, bk) in enumerate(CASES)]
    drive_many(ref)
    drive_many(dev, fuse="device")
    for (name, _hp, _bk), a, b in zip(CASES, ref, dev):
        assert b.fuse == "device", name
        assert _observable(a.runner) == _observable(b.runner), name
        assert _committed(a.runner) == _committed(b.runner), name
        assert a.exhausted == b.exhausted, name
    if cache is MISSY:
        assert len(calls) > 10
        mean = cache.mean_eval_charge()
        assert any(o.status == "error" and not o.result.times_s
                   and o.charge_s == mean
                   for b in dev for o in b.runner.memo.values()), \
            "expected committed imputed misses"


def test_fused_group_matches_isolated_runs():
    """One grouped dispatch over heterogeneous runs commits the same
    per-run state as driving each run fused on its own."""
    grouped = [_driver(n, hp, 10 + i, bk, "jax")
               for i, (n, hp, bk) in enumerate(CASES)]
    engine_jax.drive_fused(grouped)
    for i, (n, hp, bk) in enumerate(CASES):
        alone = _driver(n, hp, 10 + i, bk, "jax")
        engine_jax.drive_fused([alone])
        assert _observable(alone.runner) == _observable(grouped[i].runner)


@given(st.integers(0, 2 ** 20),
       st.sampled_from(["random_search", "genetic_algorithm", "pso",
                        "differential_evolution"]),
       st.booleans(), st.integers(1, 150), st.floats(0.02, 0.6))
@settings(max_examples=25, deadline=None)
def test_fused_parity_sweep(seed, name, by_evals, n_evals, sec_frac):
    """Random budgets exhaust mid-generation/mid-batch at arbitrary
    points; the committed prefix stays bit-identical throughout."""
    budget_kw = ({"max_evals": n_evals} if by_evals
                 else {"max_seconds": TOTAL * sec_frac})
    a = _driver(name, {}, seed, budget_kw, "numpy")
    b = _driver(name, {}, seed, budget_kw, "jax")
    drive_many([a])
    drive_many([b], fuse="device")
    assert _observable(a.runner) == _observable(b.runner)
    assert a.exhausted == b.exhausted


# ------------------------------------------------------- scores-only path
@pytest.mark.parametrize("seed", [3, 11])
def test_materialize_false_improvements_bit_identical(seed):
    """``drive_fused(materialize=False)`` never builds Observations, yet
    ``FusedRun.improvements()`` reproduces the sequential improvement
    scan of the materialized numpy trace bit-for-bit."""
    for i, (name, hp, bk) in enumerate(CASES):
        ref = _driver(name, hp, seed + i, bk, "numpy")
        drive_many([ref])
        dev = _driver(name, hp, seed + i, bk, "jax")
        (run,) = engine_jax.drive_fused([dev], materialize=False)
        assert dev.runner.trace == []  # nothing materialized
        ts, bs = run.improvements()
        ref_ts, ref_bs = _improvements_scan(ref.runner.trace)
        assert np.array_equal(ts, ref_ts), name
        assert np.array_equal(bs, ref_bs), name
        assert run.fresh_evals == ref.runner.fresh_evals, name
        assert run.spent == ref.runner.budget.spent_seconds, name


def test_improvements_matches_trace_scan():
    """``improvements()`` == scanning ``trace()`` — including the
    non-finite guard (inf failures never improve)."""
    dev = _driver("random_search", {}, 5, {"max_seconds": 1e9}, "jax")
    (run,) = engine_jax.drive_fused([dev], materialize=False)
    trace = run.trace()
    assert any(not math.isfinite(v) for _t, v, _c in trace)  # inf rows hit
    ts, bs = run.improvements()
    ref_ts, ref_bs = _improvements_scan(trace)
    assert np.array_equal(ts, ref_ts)
    assert np.array_equal(bs, ref_bs)


# -------------------------------------------- (hyperparam × seed) grid
@pytest.mark.parametrize("hp,seed", [
    ({"popsize": 10, "maxiter": 8, "method": "uniform",
      "mutation_chance": 10}, 0),
    ({"popsize": 16, "maxiter": 6, "method": "two_point",
      "mutation_chance": 20}, 7),
])
def test_evaluate_strategy_device_grid_parity(hp, seed):
    """methodology routed through the fused executor: per-(hyperparam,
    seed) scores bit-identical to the sequential drive, mode surfaced."""
    dev = evaluate_strategy(lambda: get_strategy("genetic_algorithm", **hp),
                            [make_scorer(CACHE, engine="jax")],
                            repeats=4, seed=seed, drive="device")
    seq = evaluate_strategy(lambda: get_strategy("genetic_algorithm", **hp),
                            [make_scorer(CACHE, engine="jax")],
                            repeats=4, seed=seed, drive="sequential")
    assert dev.fuse == "device"
    assert seq.fuse == "sequential"
    assert dev.score == seq.score
    assert np.array_equal(dev.curve, seq.curve)
    assert dev.fresh_evals == seq.fresh_evals
    assert dev.per_space_score == seq.per_space_score


# ------------------------------------------------------ suspend / resume
def test_snapshot_after_fused_drive_pickles_and_resumes():
    """Post-fused-drive snapshots carry no device arrays and resume into
    either engine with identical observable state."""
    dev = _driver("genetic_algorithm",
                  {"popsize": 20, "maxiter": 100, "method": "uniform",
                   "mutation_chance": 10}, 1,
                  {"max_seconds": TOTAL * 0.4}, "jax")
    drive_many([dev], fuse="device")
    payload = pickle.dumps(dev.snapshot())  # device arrays never pickle
    for eng in ("numpy", "jax"):
        runner = SimulationRunner(CACHE, Budget(max_seconds=TOTAL * 0.4),
                                  engine=eng)
        res = SearchDriver.resume(dev.strategy, CACHE.space, runner,
                                  pickle.loads(payload))
        assert _observable(res.runner) == _observable(dev.runner)


def test_mid_run_resume_finishes_fused():
    """A sequential mid-run snapshot resumes onto the device path and
    finishes bit-identically to finishing sequentially."""
    hp = {"popsize": 20, "maxiter": 100, "method": "uniform",
          "mutation_chance": 10}
    bk = {"max_evals": 137}
    ref = _driver("genetic_algorithm", hp, 9, bk, "numpy")
    cut = _driver("genetic_algorithm", hp, 9, bk, "numpy")
    for _ in range(3):
        assert ref.step() and cut.step()
    snap = pickle.loads(pickle.dumps(cut.snapshot()))
    runner = SimulationRunner(CACHE, Budget(**bk), engine="jax")
    res = SearchDriver.resume(cut.strategy, CACHE.space, runner, snap)
    drive_many([ref])
    drive_many([res], fuse="device")
    assert res.fuse == "device"
    assert _observable(ref.runner) == _observable(res.runner)


# ---------------------------------------------------- eligibility gate
def _cost_model_driver():
    """An array-native run whose runner evaluates a model, not a log."""
    space = gemm.space()
    runner = CostModelRunner(space, gemm.workload(), V5E,
                             Budget(max_evals=40))
    return SearchDriver(get_strategy("genetic_algorithm"), space, runner,
                        random.Random(0))


EMPTY = CacheFile("empty", "synth", CACHE.space, {})


@pytest.mark.parametrize("make,reason", [
    pytest.param(lambda: _driver("genetic_algorithm", {}, 0,
                                 {"max_evals": 40}, "scalar"),
                 "runner is scalar", id="scalar-runner"),
    pytest.param(_cost_model_driver, "is not a SimulationRunner",
                 id="not-simulation-runner"),
    pytest.param(lambda: _driver("genetic_algorithm", {}, 0,
                                 {"max_evals": 40}, "jax", EMPTY),
                 "cache is empty", id="empty-cache"),
    *[pytest.param(lambda n=n: _driver(n, {}, 0, {}, "jax"),
                   "unbounded budget", id=f"unbounded-{n}")
      for n in ("genetic_algorithm", "pso", "differential_evolution")],
    # random search stops asking on its own once the space is covered
    pytest.param(lambda: _driver("random_search", {}, 0, {}, "jax"), None,
                 id="unbounded-random_search"),
])
def test_fuse_reason_gates_the_device_path(make, reason):
    """Each reason the device path turns an array-native run away, and the
    one unbounded run it admits. Only the gate is asked: an unbounded GA
    driven on the host would never end."""
    got = engine_jax.fuse_reason(make())
    if reason is None:
        assert got is None
    else:
        assert got is not None and reason in got


def test_fused_unbudgeted_random_search_covers_space_exactly():
    """Random search with no budget, fused: every row exactly once, the
    recorded optimum found, and the committed state bit-identical to the
    same run on the numpy engine."""
    ref = _driver("random_search", {}, 2, {}, "numpy")
    dev = _driver("random_search", {}, 2, {}, "jax")
    drive_many([ref])
    (run,) = engine_jax.drive_fused([dev])
    assert run.fresh_evals == dev.runner.fresh_evals == N_VALID
    optimum = min(r.time_s for r in CACHE.results.values()
                  if r.status == "ok")
    assert dev.runner.best.value == optimum
    assert not run.exhausted
    assert _observable(dev.runner) == _observable(ref.runner)


# ------------------------------------------------------- fallback protocol
def test_fallback_notice_names_strategy_and_reason():
    """An ineligible (thread-bridged) strategy degrades to the host path
    with a one-time notice naming the strategy and the reason."""
    d = _driver("dual_annealing", {}, 0, {"max_evals": 40}, "jax")
    ref = _driver("dual_annealing", {}, 0, {"max_evals": 40}, "numpy")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        drive_many([ref])
        drive_many([d], fuse="device")
    notices = [w for w in caught if issubclass(w.category, FuseFallbackNotice)]
    assert len(notices) == 1  # once per (strategy, reason), not per run
    msg = str(notices[0].message)
    assert "dual_annealing" in msg and "array-native" in msg
    assert d.fuse == "host"
    assert _observable(d.runner) == _observable(ref.runner)


def test_fallback_mode_surfaces_in_report():
    """evaluate_strategy(drive="device") on an ineligible strategy ends up
    sequential — and says so on the report."""
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        rep = evaluate_strategy(lambda: get_strategy("dual_annealing"),
                                [make_scorer(CACHE, engine="jax")],
                                repeats=2, seed=0, drive="device")
    assert rep.fuse == "sequential"
    assert any(issubclass(w.category, FuseFallbackNotice) for w in caught)


def test_eligible_strategies_raise_no_notice():
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        drivers = [_driver(n, hp, 4 + i, bk, "jax")
                   for i, (n, hp, bk) in enumerate(CASES)]
        drive_many(drivers, fuse="device")
    assert not [w for w in caught
                if issubclass(w.category, FuseFallbackNotice)]
    assert all(d.fuse == "device" for d in drivers)
