"""Simulation-engine benchmark: throughput of the replay/scoring hot path.

Measures the array-backed engine against the in-tree scalar reference on a
fixed profile and emits a machine-readable report (``--json`` /
``BENCH_simulate.json`` at the repo root) that the CI ``bench`` job gates
on. Components:

  replay_fresh     full-space batch replay through ``SimulationRunner``
                   (every evaluation fresh: gather + budget + trace)
  replay_revisit   memo-hot replay (the dominant op in population
                   campaigns: strategies revisit >90 % of evaluations)
  score_trace      P_t curve sampling (Eq. 2) of a recorded trace
  baseline_small   ``make_scorer`` on a recorded-cache-sized space (the
                   1000-run virtual baseline dominates simulate cold-start)
  campaign         hypertune-style scoring of a small GA+PSO hyperparameter
                   set on hub spaces (end-to-end, warm)
  drive_many       cross-run ask fusion of the methodology's 25-repeat grid
                   (the ``core.driver.drive_many`` path): the recorded ask
                   stream of a real GA grid replayed through ``run_fused``
                   vs the scalar per-evaluation reference loop. This
                   isolates the evaluation-resolution layer the fused
                   driver owns; the component also records the end-to-end
                   grid walls (``grid_*`` fields), which are bounded at
                   ~1.2-1.9x by bit-parity itself — the strategies' own
                   RNG stepping (breeding, shuffles) must replay exactly
                   (see docs/performance.md "Why not more").
  space_compile    compiled-space construction (``core.space``): blocked
                   vectorized enumeration + both CSR neighbor tables vs
                   the frozen scalar reference
                   (``core.space.reference.ReferenceSearchSpace``):
                   recursive-DFS enumeration + per-config lazy neighbor
                   lists over the whole space. This is the one-time cost a
                   campaign pays per (space, process); the scalar side
                   used to pay it lazily, spread over every first visit.
  jax_replay       fused fresh-replay through the jitted jax engine
                   (``core.engine_jax.replay_many``): R concurrent runs'
                   full-space row permutations resolved in one vmapped
                   device dispatch vs the same workload through the numpy
                   engine's chunked row commits. Parity (accept masks,
                   trace times/values, final spends) is asserted outside
                   the timed region; the jit compile is warmed outside it
                   too. Runs on the platform JAX initialized, which the
                   report names (``backend``).
  fused_campaign   whole tuning campaigns on the device-resident fused
                   executor (``core.engine_jax.campaign.drive_fused``,
                   scores-only ``materialize=False`` consumption) vs the
                   scalar per-evaluation campaign loop, on the
                   statically-drawable tier (random-search runs whose
                   single ask pre-draws the whole row permutation, so the
                   ratio isolates the campaign loop rather than shared
                   host strategy stepping). Per-run improvements, fresh
                   evals, and budget spends are asserted bit-identical to
                   the numpy oracle outside the timed region. CI floors
                   the ratio at 10x (``check_regression.py``).
  hub_lookup       warmed ``service.ConfigHub`` exact-hit lookups (a dict
                   probe of the precomputed per-entry best) vs the naive
                   answer path a caller without the service pays per call:
                   a scan over the loaded cache's ``results.items()`` plus
                   the winning config-id decode. Both sides run from
                   memory — the service's zero-disk claim is asserted
                   outside the timed region (``disk_loads`` stays flat),
                   as is best-config parity between the two paths.
                   Shape-miss (transfer) lookup throughput is recorded as
                   informational ``transfer_*`` extras.
  surrogate        warmed modeled-tier lookups (``status="modeled"``: the
                   roofline surrogate's cached argmin, a dict probe after
                   the first call priced the space) vs re-pricing the
                   kernel's whole valid space through ``best_modeled`` on
                   every request. Answer parity and the tier itself are
                   asserted outside the timed region (docs/scenarios.md).
  local_search     neighborhood-heavy local search (greedy ILS + MLS over
                   Hamming neighborhoods) as 25-repeat fused grids: the
                   recorded per-round ask streams — whole neighborhoods as
                   compiled-space row slices — replayed fresh through
                   ``run_fused`` row commits vs the scalar per-evaluation
                   reference loop. Single-move searches (SA) are recorded
                   as informational ``sa_*`` extras: their asks are one
                   config each, so both stacks are bounded by Python call
                   overhead (~1.2x) rather than per-eval resolution work
                   (see docs/performance.md).

Every component reports vectorized and scalar wall clock plus their ratio
(``speedup``). The ratio is what CI regresses against: it is measured on
one host in one process, so it transfers across runner hardware, unlike
absolute evals/sec (also recorded, for humans). ``score_checksum`` pins
bit-exact scores: both engines must produce it, on every machine.

Usage: PYTHONPATH=src python -m benchmarks.run bench --json BENCH_simulate.json
(REPRO_FAST=1 shrinks repeats; the checksum then covers the fast profile.)
"""
from __future__ import annotations

import gc
import hashlib
import json
import random
import time

import numpy as np

from repro.core.budget import Budget, BudgetExhausted
from repro.core.cache import CachedResult, CacheFile
from repro.core.driver import SearchDriver, drive_many
from repro.core.methodology import (_repeat_rng, evaluate_strategy,
                                    make_scorer)
from repro.core.runner import SimulationRunner, run_fused
from repro.core.searchspace import SearchSpace
from repro.core.space.reference import ReferenceSearchSpace
from repro.core.strategies import get_strategy
from repro.core.tunable import tunables_from_dict

from .common import FAST

BENCH_FORMAT = "repro-bench-simulate"
BENCH_VERSION = 7  # v7: fused_campaign (device-resident campaigns);
#                         v6: surrogate (modeled tier); v5: hub_lookup
#                         (ConfigHub service); v4: jax_replay (jitted
#                         engine); v3: space_compile + local_search

# the campaign component's hyperparameter set: a slice of the Table III
# grids, small enough for CI, population-shaped so the batch step is on
CAMPAIGN_SET = (
    ("genetic_algorithm", {"popsize": 20, "maxiter": 100, "method": "uniform",
                           "mutation_chance": 10}),
    ("genetic_algorithm", {"popsize": 30, "maxiter": 50, "method": "two_point",
                           "mutation_chance": 20}),
    ("pso", {"popsize": 20, "maxiter": 100, "c1": 2.0, "c2": 1.0}),
    ("pso", {"popsize": 30, "maxiter": 50, "c1": 1.0, "c2": 0.5}),
    ("random_search", {}),
)
HUB_SELECTION = {"kernels": ["gemm", "hotspot"], "devices": ["tpu_v5e"]}
REPEATS = 3 if FAST else 10
SMALL_SPACE_N = 512


def _hub_caches() -> list[CacheFile]:
    from repro.hub import DEFAULT_ROOT, load_hub
    hub = load_hub(DEFAULT_ROOT, **HUB_SELECTION)
    return [c for _, c in sorted(hub.items())]


def _small_cache(n: int = SMALL_SPACE_N, seed: int = 7) -> CacheFile:
    """Synthetic recorded-run-sized cache (what ``repro record`` produces),
    including inf-valued failed configs."""
    rng = np.random.default_rng(seed)
    space = SearchSpace(tunables_from_dict({"x": tuple(range(n // 8)),
                                            "y": tuple(range(8))}),
                        name=f"bench{n}")
    results = {}
    vals = rng.lognormal(mean=-6, sigma=0.8, size=n)
    fail = rng.random(n) < 0.05
    for i, cfg in enumerate(space.valid_configs):
        key = space.config_id(cfg)
        if fail[i]:
            results[key] = CachedResult("error", float("inf"), (), 0.4, 0.01)
        else:
            v = float(vals[i])
            results[key] = CachedResult("ok", v, (v,) * 3, 0.3, 0.01)
    return CacheFile(f"bench{n}", "synthetic", space, results)


class _gc_paused:
    """Timed-region discipline: the replay components allocate tens of
    thousands of observations per pass, and cyclic-GC pauses land on random
    components otherwise (measured: up to 2.5x swings on the allocation-
    heavy vectorized sides). Pausing the collector for both engines keeps
    the gated ratios about the code, not the collector."""

    def __enter__(self):
        self._was_enabled = gc.isenabled()
        gc.collect()
        gc.disable()
        return self

    def __exit__(self, *exc):
        if self._was_enabled:
            gc.enable()


# --repeat N on the CLI: every component's best-of window, overridden in
# one place (None = each component's own default)
_REPEAT_OVERRIDE: "int | None" = None


def _best_of(fn, repeat: int = 5) -> float:
    repeat = _REPEAT_OVERRIDE or repeat
    best = float("inf")
    with _gc_paused():
        for _ in range(repeat):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
    return best


def _best_pair(fn_vec, fn_sca, repeat: int = 5) -> tuple:
    """Best-of walls for the two engines measured *interleaved* (vec, sca,
    vec, sca, ...) instead of in two sequential windows: shared-runner
    slowdowns come in multi-second patches, and sampling both engines
    across the same patches keeps their ratio — what CI gates on — honest
    even when absolute walls wander."""
    repeat = _REPEAT_OVERRIDE or repeat
    best_v = best_s = float("inf")
    with _gc_paused():
        for _ in range(repeat):
            t0 = time.perf_counter()
            fn_vec()
            best_v = min(best_v, time.perf_counter() - t0)
            t0 = time.perf_counter()
            fn_sca()
            best_s = min(best_s, time.perf_counter() - t0)
    return best_v, best_s


def _component(wall_vec: float, wall_scalar: float, **extra) -> dict:
    return {"wall_s": wall_vec, "wall_s_scalar": wall_scalar,
            "speedup": wall_scalar / max(wall_vec, 1e-12), **extra}


def bench_replay(cache: CacheFile) -> tuple[dict, dict]:
    configs = cache.space.valid_configs
    cache.columns  # build outside the timed region (one-time, amortized)

    def fresh(columnar):
        def go():
            r = SimulationRunner(cache, Budget(max_seconds=float("inf")),
                                 columnar=columnar)
            r.run_batch(configs)
        return go

    w_vec, w_sca = _best_pair(fresh(True), fresh(False))
    fresh_c = _component(w_vec, w_sca,
                         evals_per_sec=len(configs) / w_vec,
                         evals_per_sec_scalar=len(configs) / w_sca,
                         n_evals=len(configs))

    def revisit(columnar):
        r = SimulationRunner(cache, Budget(max_seconds=float("inf")),
                             columnar=columnar)
        r.run_batch(configs)  # warm the memo

        def go():
            r.run_batch(configs)
        return go

    w_vec, w_sca = _best_pair(revisit(True), revisit(False))
    revisit_c = _component(w_vec, w_sca,
                           evals_per_sec=len(configs) / w_vec,
                           evals_per_sec_scalar=len(configs) / w_sca,
                           n_evals=len(configs))
    return fresh_c, revisit_c


def bench_score_trace(cache: CacheFile) -> dict:
    sc_vec = make_scorer(cache, engine="vectorized")
    sc_sca = make_scorer(cache, engine="scalar")
    times = sc_vec.sample_times()
    baseline = sc_vec.baseline_at_time(times)
    # a recorded random-search trace: replay a permutation to budget
    runner = SimulationRunner(cache, Budget(max_seconds=sc_vec.budget_s))
    get_strategy("random_search").run(cache.space, runner, random.Random(0))
    trace = runner.trace
    calls = 200

    def go(sc):
        def run():
            for _ in range(calls):
                sc.score_trace(trace, times, baseline)
        return run

    w_vec, w_sca = _best_pair(go(sc_vec), go(sc_sca))
    return _component(w_vec, w_sca, calls_per_sec=calls / w_vec,
                      calls_per_sec_scalar=calls / w_sca,
                      trace_len=len(trace))


def bench_baseline_small() -> dict:
    w_vec, w_sca = _best_pair(
        lambda: make_scorer(_small_cache(), engine="vectorized"),
        lambda: make_scorer(_small_cache(), engine="scalar"))
    return _component(w_vec, w_sca, n_configs=SMALL_SPACE_N)


def bench_campaign() -> dict:
    walls, evals, scores = {}, {}, {}
    # fresh caches per engine: spaces memoize compiled tables / ids as
    # they are exercised, so sharing objects would hand the second
    # engine a warm cache and skew the ratio
    scorers = {engine: [make_scorer(c, engine=engine)
                        for c in _hub_caches() + [_small_cache()]]
               for engine in ("vectorized", "scalar")}
    # best of two passes per engine, engines interleaved (see _best_pair):
    # the second pass runs against warm space caches — what a long
    # campaign actually sees — and interleaving keeps host-noise patches
    # out of the gated ratio
    with _gc_paused():
        for _pass in range(2):
            for engine in ("vectorized", "scalar"):
                t0 = time.perf_counter()
                fresh = 0
                engine_scores = {}
                for strat, hp in CAMPAIGN_SET:
                    rep = evaluate_strategy(
                        lambda: get_strategy(strat, **hp),
                        scorers[engine], repeats=REPEATS, seed=0)
                    fresh += rep.fresh_evals
                    hp_id = ",".join(f"{k}={hp[k]}" for k in sorted(hp))
                    engine_scores[f"{strat}({hp_id})"] = rep.score
                wall = time.perf_counter() - t0
                walls[engine] = min(walls.get(engine, float("inf")), wall)
                evals[engine] = fresh
                scores[engine] = engine_scores
    if scores["vectorized"] != scores["scalar"]:
        raise AssertionError(
            "engine parity violation: vectorized and scalar campaigns "
            f"disagree: {scores}")
    checksum = hashlib.sha256(json.dumps(
        {k: repr(v) for k, v in sorted(scores["vectorized"].items())},
        sort_keys=True).encode()).hexdigest()
    return _component(
        walls["vectorized"], walls["scalar"],
        evals_per_sec=evals["vectorized"] / walls["vectorized"],
        evals_per_sec_scalar=evals["scalar"] / walls["scalar"],
        fresh_evals=evals["vectorized"], repeats=REPEATS,
        scores=scores["vectorized"], score_checksum=checksum)


DRIVE_MANY_REPEATS = 25  # the methodology's repeat count (paper Sec. III-B)
DRIVE_MANY_STRATEGY = "genetic_algorithm"


def _harvest_grid_stream(cache: CacheFile, budget_s: float, seed: int,
                         strategy: str = None,
                         hyperparams: dict = None) -> tuple:
    """Drive one real ``DRIVE_MANY_REPEATS``-run strategy grid (the
    ``drive_many`` path, same per-cell RNG seeding as ``run_repeat``) and
    record its per-round ask stream plus the reference traces. Asks are
    kept in their native form — ``core.space.RowBatch`` since the
    index-native refactor — so replays exercise the row path the real
    driver uses, while the scalar reference simply iterates them into
    value tuples."""
    scorer_name = f"{cache.kernel}@{cache.device}"

    class _Named:  # _repeat_rng seeds from the scorer's name
        name = scorer_name

    drivers = [SearchDriver(get_strategy(strategy or DRIVE_MANY_STRATEGY,
                                         **(hyperparams or {})),
                            cache.space,
                            SimulationRunner(cache,
                                             Budget(max_seconds=budget_s)),
                            _repeat_rng(_Named, r, seed))
               for r in range(DRIVE_MANY_REPEATS)]
    rounds: list[list[tuple[int, list]]] = []
    active = list(range(len(drivers)))
    while active:
        entries = []
        for i in active:
            d = drivers[i]
            configs = d.strategy.ask(d.state)
            if not configs:
                d.state.finished = True
                continue
            entries.append((i, configs))
        if not entries:
            break
        results = run_fused([(drivers[i].runner, cfgs)
                             for i, cfgs in entries])
        survivors = []
        for (i, cfgs), res in zip(entries, results):
            if isinstance(res, BudgetExhausted):
                drivers[i].state.finished = True
            else:
                drivers[i].strategy.tell(drivers[i].state, res)
                survivors.append(i)
        rounds.append(entries)
        active = survivors
    for d in drivers:
        d.state.close()
    return rounds, [list(d.runner.trace) for d in drivers]


def bench_drive_many(caches: "list[CacheFile]") -> dict:
    """Fused cross-run resolution of the methodology's repeat grid.

    Harvests the per-round ask streams of real GA repeat grids on the hub
    spaces, then times those exact evaluation streams through (a)
    ``run_fused`` on columnar runners and (b) the scalar per-evaluation
    reference loop — asserting observation-for-observation trace parity
    between the two outside the timed region. The grids' end-to-end walls
    (strategy stepping included) are recorded as ``grid_*`` extras.
    """
    # three grid seeds per space: triple the measured stream, shrinking
    # the relative timing noise CI gates against
    harvests = [(c, b, _harvest_grid_stream(c, b, seed))
                for c, b in ((c, make_scorer(c).budget_s) for c in caches)
                for seed in (0, 1, 2)]
    n_evals = sum(len(cfgs) for _, _, (rounds, _) in harvests
                  for entries in rounds for _, cfgs in entries)

    def replay(columnar: bool) -> list:
        all_runners = []
        for cache, budget_s, (rounds, _) in harvests:
            runners = [SimulationRunner(cache,
                                        Budget(max_seconds=budget_s),
                                        columnar=columnar)
                       for _ in range(DRIVE_MANY_REPEATS)]
            if columnar:
                for entries in rounds:
                    run_fused([(runners[i], cfgs) for i, cfgs in entries])
            else:
                for entries in rounds:
                    for i, cfgs in entries:
                        run = runners[i].run
                        try:
                            for c in cfgs:
                                run(c)
                        except BudgetExhausted:
                            pass
            all_runners.append(runners)
        return all_runners

    for columnar in (True, False):  # parity outside the timed region
        for runners, (_, _, (_, refs)) in zip(replay(columnar), harvests):
            for runner, ref in zip(runners, refs):
                assert runner.trace == ref, \
                    "drive_many parity violation: fused replay diverged"
    w_vec, w_sca = _best_pair(lambda: replay(True), lambda: replay(False),
                              repeat=9)

    # -- end-to-end grid walls (strategy stepping included), informational
    def grid(engine: str, drive: str) -> float:
        scorers = [make_scorer(c, engine=engine) for c in caches]
        t0 = time.perf_counter()
        evaluate_strategy(lambda: get_strategy(DRIVE_MANY_STRATEGY),
                          scorers, repeats=DRIVE_MANY_REPEATS, seed=0,
                          drive=drive)
        return time.perf_counter() - t0

    grid_vec = min(grid("vectorized", "fused") for _ in range(3))
    grid_sca = min(grid("scalar", "sequential") for _ in range(3))
    return _component(w_vec, w_sca,
                      evals_per_sec=n_evals / w_vec,
                      evals_per_sec_scalar=n_evals / w_sca,
                      n_evals=n_evals,
                      n_rounds=sum(len(r) for _, _, (r, _) in harvests),
                      n_runs=DRIVE_MANY_REPEATS * len(harvests),
                      strategy=DRIVE_MANY_STRATEGY,
                      grid_wall_s=grid_vec, grid_wall_s_scalar=grid_sca,
                      grid_speedup=grid_sca / max(grid_vec, 1e-12))


def bench_space_compile(caches: "list[CacheFile]") -> dict:
    """Compiled-space construction vs the frozen scalar reference.

    vec:    ``SearchSpace.compiled`` (blocked vectorized enumeration with
            the membership fast path) plus both CSR neighbor tables;
    scalar: ``ReferenceSearchSpace`` recursive-DFS enumeration plus lazy
            neighbor lists for every valid config in both semantics — the
            work the old implementation spread over every first visit of a
            campaign, here paid in one measurable lump.
    Fresh space objects per timed pass (this is a cold-start component).
    """
    specs = [(c.space.tunables, c.space.constraints, c.space.name)
             for c in caches]
    n_valid = 0

    def vec():
        nonlocal n_valid
        n_valid = 0
        for tun, cons, name in specs:
            cs = SearchSpace(tun, cons, name).compiled
            cs.csr(strictly_adjacent=False)
            cs.csr(strictly_adjacent=True)
            n_valid += cs.n_valid

    def sca():
        for tun, cons, name in specs:
            space = ReferenceSearchSpace(tun, cons, name)
            for cfg in space.valid_configs:
                space.neighbors(cfg)
                space.neighbors(cfg, strictly_adjacent=True)

    w_vec, w_sca = _best_pair(vec, sca, repeat=3)
    return _component(w_vec, w_sca, n_valid=n_valid, n_spaces=len(specs),
                      configs_per_sec=n_valid / w_vec,
                      configs_per_sec_scalar=n_valid / w_sca)


# neighborhood-heavy local searches: whole Hamming neighborhoods per ask
LOCAL_SEARCH_SET = (("greedy_ils", {}), ("mls", {"adjacent_only": False}))
LOCAL_SEARCH_SINGLE = ("simulated_annealing", {})  # informational extras


def bench_local_search(caches: "list[CacheFile]") -> dict:
    """Fresh-replay of neighborhood-heavy local-search grids.

    Harvests the per-round ask streams of real 25-repeat greedy-ILS and
    Hamming-MLS grids (whole neighborhoods as compiled-space row slices),
    then times those exact streams through (a) ``run_fused`` row commits
    on columnar runners and (b) the scalar per-evaluation reference loop,
    asserting trace parity outside the timed region — the local-search
    analogue of ``bench_drive_many``. Simulated annealing's single-move
    stream is measured the same way and reported as ``sa_*`` extras: one
    config per ask leaves both stacks bound by Python call overhead, so
    its ratio is informational, not gated.
    """
    def harvests_for(specs) -> list:
        # three grid seeds per (space, strategy): triple the measured
        # stream, shrinking the relative timing noise CI gates against
        return [(c, b, _harvest_grid_stream(c, b, seed, strategy=s,
                                            hyperparams=hp))
                for c, b in ((c, make_scorer(c).budget_s) for c in caches)
                for s, hp in specs
                for seed in (0, 1, 2)]

    def replay(harvests, columnar: bool) -> list:
        all_runners = []
        for cache, budget_s, (rounds, _) in harvests:
            runners = [SimulationRunner(cache,
                                        Budget(max_seconds=budget_s),
                                        columnar=columnar)
                       for _ in range(DRIVE_MANY_REPEATS)]
            if columnar:
                for entries in rounds:
                    run_fused([(runners[i], cfgs) for i, cfgs in entries])
            else:
                for entries in rounds:
                    for i, cfgs in entries:
                        run = runners[i].run
                        try:
                            for c in cfgs:
                                run(c)
                        except BudgetExhausted:
                            pass
            all_runners.append(runners)
        return all_runners

    def measure(harvests) -> tuple:
        for columnar in (True, False):  # parity outside the timed region
            for runners, (_, _, (_, refs)) in zip(
                    replay(harvests, columnar), harvests):
                for runner, ref in zip(runners, refs):
                    assert runner.trace == ref, \
                        "local_search parity violation: replay diverged"
        w_vec, w_sca = _best_pair(lambda: replay(harvests, True),
                                  lambda: replay(harvests, False),
                                  repeat=9)
        n = sum(len(cfgs) for _, _, (rounds, _) in harvests
                for entries in rounds for _, cfgs in entries)
        return w_vec, w_sca, n

    main_harvests = harvests_for(LOCAL_SEARCH_SET)
    w_vec, w_sca, n_evals = measure(main_harvests)
    sa_vec, sa_sca, sa_evals = measure(harvests_for([LOCAL_SEARCH_SINGLE]))
    return _component(w_vec, w_sca,
                      evals_per_sec=n_evals / w_vec,
                      evals_per_sec_scalar=n_evals / w_sca,
                      n_evals=n_evals,
                      strategies=[s for s, _ in LOCAL_SEARCH_SET],
                      n_runs=DRIVE_MANY_REPEATS * len(main_harvests),
                      sa_wall_s=sa_vec, sa_wall_s_scalar=sa_sca,
                      sa_speedup=sa_sca / max(sa_vec, 1e-12),
                      sa_n_evals=sa_evals)


HUB_LOOKUP_CALLS = 100  # lookups per target per timed pass


def bench_hub_lookup() -> dict:
    """Warmed ``ConfigHub`` exact hits vs the naive per-call answer path.

    vec:    ``ConfigHub.lookup`` on a warmed service — after the entry's
            one-time materialization an exact hit is a dict probe of the
            precomputed best (the microsecond claim ``service`` makes);
    scalar: what a caller without the service pays on every request even
            with the cache already in memory: a full scan over
            ``results.items()`` for the fastest ok config plus the winning
            config-id decode.
    Parity (best config and value) and the zero-disk claim (``disk_loads``
    flat across the timed passes) are asserted outside the timed region.
    Shape-miss lookups — donor search over the index plus a cached best —
    are timed as informational ``transfer_*`` extras, not gated.
    """
    from repro.hub import DEFAULT_ROOT
    from repro.service import ConfigHub
    hub = ConfigHub(DEFAULT_ROOT)
    caches = {(c.kernel, c.device): c for c in _hub_caches()}
    targets = sorted(caches)

    def naive_best(cache: CacheFile) -> tuple:
        best_key, best_v = None, float("inf")
        for key, res in cache.results.items():
            if res.status == "ok" and res.time_s < best_v:
                best_v, best_key = res.time_s, key
        cfg = cache.space.as_dict(cache.space.config_from_id(best_key))
        return cfg, best_v

    for kernel, device in targets:  # warm-up + parity, outside timed region
        r = hub.lookup(kernel, device=device)
        cfg, val = naive_best(caches[(kernel, device)])
        assert r.status == "exact" and (r.best_config, r.best_value) \
            == (cfg, val), f"hub_lookup parity violation: {kernel}@{device}"
    loads = hub.disk_loads

    def vec():
        for _ in range(HUB_LOOKUP_CALLS):
            for kernel, device in targets:
                hub.lookup(kernel, device=device)

    def sca():
        for _ in range(HUB_LOOKUP_CALLS):
            for kernel, device in targets:
                naive_best(caches[(kernel, device)])

    w_vec, w_sca = _best_pair(vec, sca)
    assert hub.disk_loads == loads, \
        "hub_lookup: warmed exact hits touched disk"
    n_lookups = HUB_LOOKUP_CALLS * len(targets)

    # -- transfer throughput (shape miss -> nearest donor), informational
    miss = {"m": 2048}
    assert hub.lookup("gemm", miss).status == "transfer"  # donor warmed

    def transfer():
        for _ in range(HUB_LOOKUP_CALLS):
            hub.lookup("gemm", miss)

    w_tr = _best_of(transfer)
    return _component(w_vec, w_sca,
                      lookups_per_sec=n_lookups / w_vec,
                      lookups_per_sec_scalar=n_lookups / w_sca,
                      n_lookups=n_lookups, n_entries=len(targets),
                      transfer_wall_s=w_tr,
                      transfer_per_sec=HUB_LOOKUP_CALLS / w_tr)


SURROGATE_CALLS = 100  # modeled lookups per timed pass


def bench_surrogate() -> dict:
    """Warmed modeled-tier lookups vs re-pricing the space per call.

    vec:    ``ConfigHub.lookup`` on a triple with no recorded entry —
            the first call prices the kernel's valid space through the
            roofline surrogate and caches the answer per (kernel, device,
            problem key); every later hit is a dict probe;
    scalar: what a caller without that cache pays per request:
            ``best_modeled`` re-prices the whole valid space (the
            flash-attention default space) every time.
    Answer parity (the cached best is the argmin re-pricing finds) and the
    tier itself (``status == "modeled"`` with model provenance) are
    asserted outside the timed region.
    """
    from repro.hub import DEFAULT_ROOT, hub_default_problem
    from repro.scenarios import best_modeled
    from repro.service import ConfigHub
    hub = ConfigHub(DEFAULT_ROOT)
    kernel, device = "flash_attention", "tpu_v6e"
    # a bare lookup resolves to the hub-default shape; hand the same
    # shape to the re-pricing side (None would mean the SMOKE shape)
    problem = dict(hub_default_problem(kernel))

    r = hub.lookup(kernel, device=device)  # warm-up, outside timed region
    mb = best_modeled(kernel, problem, device)
    assert r.status == "modeled" and r.model, \
        f"surrogate: expected a modeled answer, got {r.status!r}"
    assert (r.best_config, r.best_value) == (dict(mb.config), mb.value), \
        "surrogate parity violation: cached answer != re-priced argmin"

    def vec():
        for _ in range(SURROGATE_CALLS):
            hub.lookup(kernel, device=device)

    def sca():
        for _ in range(SURROGATE_CALLS):
            best_modeled(kernel, problem, device)

    w_vec, w_sca = _best_pair(vec, sca)
    return _component(w_vec, w_sca,
                      lookups_per_sec=SURROGATE_CALLS / w_vec,
                      lookups_per_sec_scalar=SURROGATE_CALLS / w_sca,
                      n_lookups=SURROGATE_CALLS, n_configs=mb.n_valid,
                      model=mb.model, dominant=mb.dominant)


JAX_REPLAY_RUNS = 64  # concurrent runs in the fused vmapped dispatch


def bench_jax_replay(cache: CacheFile) -> dict:
    """Fused fresh-replay on the jitted jax engine vs the numpy engine.

    ``JAX_REPLAY_RUNS`` independent full-space row permutations resolve as
    one ``replay_many`` dispatch (gathers + per-run budget scans, vmapped);
    the numpy side replays the identical workload through each runner's
    chunked whole-array row commits. Both sides are pure fresh replay
    (unlimited budget) — the throughput claim ``engine_jax`` makes. The
    ``speedup`` ratio is measured same-host/same-process like every other
    component, so the CI floor transfers across runner silicon.
    """
    import jax

    from repro.core import engine_jax
    from repro.core.space import RowBatch

    compiled = cache.space.compiled
    cols = cache.columns
    n = compiled.n_valid
    rng = np.random.default_rng(0)
    rows = np.stack([rng.permutation(n)
                     for _ in range(JAX_REPLAY_RUNS)]).astype(np.int64)
    n_evals = JAX_REPLAY_RUNS * n
    tables = engine_jax.replay_tables(cols, compiled)

    def jax_side():
        # host arrays: the copy back waits for the device
        return engine_jax.replay_many(cols, compiled, rows, tables=tables)

    def numpy_side():
        runners = []
        for r in range(JAX_REPLAY_RUNS):
            runner = SimulationRunner(cache,
                                      Budget(max_seconds=float("inf")))
            runner.run_batch(RowBatch(compiled, rows[r]))
            runners.append(runner)
        return runners

    # parity outside the timed region: every run's committed trace and
    # final spend must match the device arrays bit-for-bit
    out = jax_side()  # also warms the jit compile
    accept, t_after, value, _c, spent, evals, _x = (np.asarray(o)
                                                    for o in out)
    for r, runner in enumerate(numpy_side()):
        assert accept[r].all() and runner.budget.spent_evals == evals[r]
        assert runner.budget.spent_seconds == spent[r], \
            "jax_replay parity violation: spends diverged"
        trace_t = np.fromiter((t for t, _v, _cfg in runner.trace),
                              dtype=np.float64, count=n)
        trace_v = np.fromiter((v for _t, v, _cfg in runner.trace),
                              dtype=np.float64, count=n)
        assert np.array_equal(trace_t, t_after[r]) \
            and np.array_equal(trace_v, value[r]), \
            "jax_replay parity violation: traces diverged"

    w_jax, w_np = _best_pair(jax_side, numpy_side)
    return _component(w_jax, w_np,
                      evals_per_sec=n_evals / w_jax,
                      evals_per_sec_scalar=n_evals / w_np,
                      n_evals=n_evals, n_runs=JAX_REPLAY_RUNS,
                      reference="numpy",
                      backend=jax.devices()[0].platform)


FUSED_CAMPAIGN_RUNS = 4  # seeds per space in the fused-campaign grid


def bench_fused_campaign() -> dict:
    """Whole tuning campaigns on the device-resident fused executor vs
    the scalar per-evaluation campaign loop.

    The workload is the statically-drawable tier — random-search runs
    that pre-draw their whole row permutation in one ask, so neither side
    pays per-generation strategy stepping and the ratio isolates the
    campaign loop itself: per-evaluation Python resolution (scalar
    ``drive_many``) vs a handful of vmapped replay dispatches plus
    array-native improvement extraction (``drive_fused`` with
    ``materialize=False``, the scores-only consumption ``methodology``
    uses). Population strategies (GA/PSO/DE) are deliberately absent:
    their host ask/tell stepping is shared by both sides and bounds the
    end-to-end ratio near 1x (see docs/performance.md, "host↔device
    round-trip budget") — the ``campaign`` component already covers that
    regime end to end.

    Parity is asserted outside the timed region: every fused run's
    improvement step function, fresh-eval count, and committed budget
    spend must equal the numpy engine's ``drive_many`` result
    bit-for-bit.
    """
    import jax

    from repro.core import engine_jax
    caches = _hub_caches() + [_small_cache()]
    for c in caches:
        c.columns  # mirrors + compiled spaces built outside timed region
        c.space.compiled
    n_evals = sum(c.space.compiled.n_valid
                  for c in caches) * FUSED_CAMPAIGN_RUNS

    def _drivers():
        ds = []
        for c in caches:
            for r in range(FUSED_CAMPAIGN_RUNS):
                runner = SimulationRunner(c, Budget(max_seconds=1e9))
                ds.append(SearchDriver(get_strategy("random_search"),
                                       c.space, runner,
                                       random.Random(1000 + r)))
        return ds

    def fused_side():
        drivers = _drivers()
        for d in drivers:
            d.runner.engine = "jax"
        engine_jax.drive_fused(drivers, materialize=False)

    def scalar_side():
        drive_many(_drivers(), engine="scalar")

    # parity outside the timed region (also warms the jit dispatches):
    # fused improvements == the numpy oracle's sequential improvement scan
    ref = _drivers()
    drive_many(ref, engine="numpy")
    dev = _drivers()
    for d in dev:
        d.runner.engine = "jax"
    runs = engine_jax.drive_fused(dev, materialize=False)
    for r, run in zip(ref, runs):
        ts, bs = run.improvements()
        best, rts, rbs = float("inf"), [], []
        for t, v, _cfg in r.runner.trace:
            if v < best:
                best = v
                rts.append(t)
                rbs.append(v)
        assert np.array_equal(ts, np.asarray(rts)) \
            and np.array_equal(bs, np.asarray(rbs)), \
            "fused_campaign parity violation: improvements diverged"
        assert run.fresh_evals == r.runner.fresh_evals \
            and run.spent == r.runner.budget.spent_seconds, \
            "fused_campaign parity violation: spends diverged"

    w_fused, w_scalar = _best_pair(fused_side, scalar_side, repeat=3)
    return _component(w_fused, w_scalar,
                      evals_per_sec=n_evals / w_fused,
                      evals_per_sec_scalar=n_evals / w_scalar,
                      n_evals=n_evals,
                      n_runs=len(caches) * FUSED_CAMPAIGN_RUNS,
                      reference="scalar",
                      backend=jax.devices()[0].platform)


ALL_COMPONENTS = ("replay_fresh", "replay_revisit", "score_trace",
                  "baseline_small", "campaign", "drive_many",
                  "space_compile", "local_search", "jax_replay",
                  "fused_campaign", "hub_lookup", "surrogate")


def run_bench(components: "list[str] | None" = None) -> dict:
    """The full report, or — ``components`` given — just those components
    (``--component`` on the CLI: iterate on one ratio without paying for
    the whole profile). A filtered report is for humans; the committed
    baseline the CI gate compares against is always the full run."""
    if components:
        unknown = sorted(set(components) - set(ALL_COMPONENTS))
        if unknown:
            raise ValueError(f"unknown bench components {unknown}; "
                             f"known: {list(ALL_COMPONENTS)}")
        selected = [c for c in ALL_COMPONENTS if c in set(components)]
    else:
        selected = list(ALL_COMPONENTS)
    hub = _hub_caches()
    big = hub[0]  # gemm@tpu_v5e: the largest hub space
    comp: dict = {}
    if {"replay_fresh", "replay_revisit"} & set(selected):
        fresh_c, revisit_c = bench_replay(big)  # shares one cache build
        if "replay_fresh" in selected:
            comp["replay_fresh"] = fresh_c
        if "replay_revisit" in selected:
            comp["replay_revisit"] = revisit_c
    makers = {
        "score_trace": lambda: bench_score_trace(big),
        "baseline_small": bench_baseline_small,
        "campaign": bench_campaign,
        "drive_many": lambda: bench_drive_many(hub),
        "space_compile": lambda: bench_space_compile(hub),
        "local_search": lambda: bench_local_search(hub),
        "jax_replay": lambda: bench_jax_replay(big),
        "fused_campaign": bench_fused_campaign,
        "hub_lookup": bench_hub_lookup,
        "surrogate": bench_surrogate,
    }
    for name in selected:
        if name not in comp:
            comp[name] = makers[name]()
    report = {
        "format": BENCH_FORMAT,
        "version": BENCH_VERSION,
        "profile": {
            "fast": FAST,
            "repeats": REPEATS,
            "hub": HUB_SELECTION,
            "small_space": SMALL_SPACE_N,
            "campaign_set": [f"{s}:{sorted(hp.items())}"
                             for s, hp in CAMPAIGN_SET],
            "drive_many": {"repeats": DRIVE_MANY_REPEATS,
                           "strategy": DRIVE_MANY_STRATEGY},
            "local_search": {"repeats": DRIVE_MANY_REPEATS,
                             "strategies": [f"{s}:{sorted(hp.items())}"
                                            for s, hp in LOCAL_SEARCH_SET]},
            "jax_replay": {"runs": JAX_REPLAY_RUNS},
            "fused_campaign": {"runs_per_space": FUSED_CAMPAIGN_RUNS},
            "hub_lookup": {"calls": HUB_LOOKUP_CALLS},
            "surrogate": {"calls": SURROGATE_CALLS},
        },
        "components": comp,
    }
    if "campaign" in comp:
        report["score_checksum"] = comp["campaign"]["score_checksum"]
    if "replay_fresh" in comp:
        report["evals_per_sec"] = comp["replay_fresh"]["evals_per_sec"]
    # headline: geometric mean of the per-component engine speedups
    speedups = [c["speedup"] for c in comp.values() if "speedup" in c]
    if speedups:
        report["speedup_geomean"] = float(np.exp(np.mean(np.log(speedups))))
    return report


def main(json_out: str | None = None,
         components: "list[str] | None" = None,
         repeat: "int | None" = None) -> dict:
    global _REPEAT_OVERRIDE
    if repeat is not None:
        if repeat < 1:
            raise ValueError(f"--repeat must be >= 1, got {repeat}")
        _REPEAT_OVERRIDE = repeat
    try:
        report = run_bench(components)
    finally:
        _REPEAT_OVERRIDE = None
    comp = report["components"]
    print(f"{'component':16s} "
          f"{'vectorized':>12s} {'scalar':>12s} {'speedup':>8s}")
    for name, c in comp.items():
        print(f"{name:16s} {c['wall_s']*1e3:10.1f}ms {c['wall_s_scalar']*1e3:10.1f}ms "
              f"{c['speedup']:7.2f}x")
    if "replay_fresh" in comp and "replay_revisit" in comp:
        print(f"replay throughput: "
              f"{comp['replay_fresh']['evals_per_sec']:,.0f} "
              f"fresh evals/s, {comp['replay_revisit']['evals_per_sec']:,.0f} "
              f"revisits/s")
    if "campaign" in comp:
        print(f"campaign: {comp['campaign']['evals_per_sec']:,.0f} "
              f"fresh evals/s ({comp['campaign']['fresh_evals']} evals)")
    if "fused_campaign" in comp:
        print(f"fused campaign: "
              f"{comp['fused_campaign']['evals_per_sec']:,.0f} fresh "
              f"evals/s ({comp['fused_campaign']['n_evals']} evals, "
              f"{comp['fused_campaign']['speedup']:.1f}x over scalar)")
    if "speedup_geomean" in report:
        print(f"geomean engine speedup: {report['speedup_geomean']:.2f}x")
    if "score_checksum" in report:
        print(f"score checksum: {report['score_checksum'][:16]}…")
    if json_out:
        with open(json_out, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"wrote {json_out}")
    return report


if __name__ == "__main__":
    main()
