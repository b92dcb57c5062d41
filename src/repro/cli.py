"""Unified command-line interface: ``python -m repro <subcommand>``.

One entry point for the paper's workflow, replacing the ad-hoc scripts in
``examples/`` and ``benchmarks/`` for everyday use:

  simulate   score one strategy (fixed hyperparameters) with the
             methodology in simulation mode (paper Sec. III-B/C, Eqs. 2–3)
  hypertune  exhaustive hyperparameter-grid campaign (Sec. IV-B,
             Table III) — parallel (``--workers``) and resumable
             (``--journal``)
  meta       meta-strategy hyperparameter optimization (Sec. IV-C,
             Table IV / Eq. 4), journaled for resume
  report     inspect a campaign journal: ranking, optimal-vs-average
             improvement (the 94.8 % metric), wall-clock parallelism
  spaces     per-space statistics for the selected hub/cache spaces and
             the strategies' hyperparameter grids: cartesian vs valid
             size, valid fraction, neighbor-degree distribution, compile
             time (the ``core.space`` compiled representation)
  record     strategy-sample a registered Pallas kernel (live on the JAX
             device, or a cost model) across parallel workers and emit a
             replayable T4 cache — producing the FAIR data the simulation
             mode consumes (Sec. III-C/D)
  bruteforce exhaustively record a registered kernel's whole valid space
             (the paper's Table II hub-building runs), resumable per shard
  merge-cache fold recording shards (from crashed/partial/parallel runs)
             into one canonical cache file — ``--hub-root`` also registers
             the merge into a hub and evicts stale service index entries
  lookup     best known config for (kernel, problem shape, device) from
             the recorded hub: exact hit, nearest-shape transfer with
             confidence, roofline-modeled answer, or cold
             (docs/service.md, docs/scenarios.md)
  serve      line-oriented lookup service: JSON requests on stdin, one
             ``LookupResult`` JSON per line on stdout
  scenarios  the scenario matrix: every (kernel × shape × device) triple
             with its coverage tier (recorded | modeled | cold), optional
             best times, JSON artifact output, and the recorded best-time
             regression gate (docs/scenarios.md)
  fleet      run/resume the recording fleet over the scenario matrix:
             record → merge → register each runnable triple into the hub,
             journaled so re-runs skip completed work
  hub        hub dataset management: build, info, verify (sha256 every
             indexed file), stats (includes the coverage matrix)
  lint       parity-lint: static analysis of the determinism / pickle /
             f64 / protocol contracts (docs/static-analysis.md); the CI
             gate is ``python -m repro lint src/repro``

Search spaces come either from the benchmark hub (``--kernels/--devices``
or ``--split``, Sec. III-D) or from explicit T4 cache files (``--cache``)
— including caches produced by ``record``/``bruteforce``.
"""
from __future__ import annotations

import argparse
import ast
import os
import sys
from typing import Sequence

from .api import Tuner
from .core.hypertuner import (HyperConfigResult, HyperTuningResult,
                              hyperparam_searchspace)
from .core.parallel import CampaignJournal, report_from_json
from .core.strategies import STRATEGIES


# ------------------------------------------------------------ shared options
def _add_space_args(p: argparse.ArgumentParser) -> None:
    g = p.add_argument_group("search spaces (scoring data)")
    g.add_argument("--cache", action="append", default=[], metavar="PATH",
                   help="T4 cache file (.json/.json.gz/.json.zst); "
                        "repeatable. Overrides the hub options.")
    g.add_argument("--split", choices=("train", "test"), default="train",
                   help="hub device split (paper Sec. III-D; default train)")
    g.add_argument("--kernels", default=None,
                   help="comma-separated hub kernels (default: all)")
    g.add_argument("--devices", default=None,
                   help="comma-separated hub devices (overrides --split)")
    g.add_argument("--hub-root", default=None,
                   help="hub directory (default: the bundled hub path)")
    g.add_argument("--engine", choices=("vectorized", "scalar", "jax"),
                   default="vectorized",
                   help="simulation engine: 'vectorized' resolves lookups "
                        "and scoring through columnar numpy arrays; "
                        "'scalar' is the per-evaluation reference path; "
                        "'jax' runs the budget replay on the JAX device "
                        "(whole campaigns per dispatch for GA, PSO, DE "
                        "and random search), all of it in this process, "
                        "which holds the device: --workers does not apply. "
                        "Scores are bit-identical across all three (see "
                        "docs/performance.md)")


def _add_exec_args(p: argparse.ArgumentParser) -> None:
    g = p.add_argument_group("execution")
    g.add_argument("--workers", type=int, default=1,
                   help="worker pool size (1 = serial; results are "
                        "bit-identical at any worker count)")
    g.add_argument("--backend", choices=("auto", "thread", "process"),
                   default="auto", help="worker pool backend")
    g.add_argument("--repeats", type=int, default=25,
                   help="methodology repeats per space (paper uses 25)")
    g.add_argument("--seed", type=int, default=0)


def _parse_hyperparams(text: str | None) -> dict:
    """Parse ``k=v,k2=v2`` with Python-literal values (``0.05``, ``True``,
    ``'greedy'``); bare words fall back to strings."""
    out: dict = {}
    for item in filter(None, (text or "").split(",")):
        key, _, raw = item.partition("=")
        if not _:
            raise SystemExit(f"--hyperparams: expected k=v, got {item!r}")
        try:
            out[key.strip()] = ast.literal_eval(raw.strip())
        except (ValueError, SyntaxError):
            out[key.strip()] = raw.strip()
    return out


def _progress(quiet: bool):
    if quiet:
        return None
    return lambda msg: print(msg, flush=True)


def tuner_from_args(args) -> Tuner:
    """Build the ``repro.api.Tuner`` facade from the shared CLI options
    (paper Sec. III-B: one scorer per brute-forced search space)."""
    return Tuner(
        caches=args.cache or None,
        kernels=args.kernels.split(",") if args.kernels else None,
        devices=args.devices.split(",") if args.devices else None,
        split=args.split,
        hub_root=args.hub_root,
        engine=getattr(args, "engine", "vectorized"),
        repeats=args.repeats,
        seed=args.seed,
        workers=args.workers,
        backend=args.backend,
        progress=_progress(getattr(args, "quiet", False)),
    )


# -------------------------------------------------------------- subcommands
def cmd_simulate(args) -> int:
    """Score one strategy configuration (paper Sec. III-B, Eqs. 2–3)."""
    with tuner_from_args(args) as tuner:
        run = tuner.simulate(args.strategy,
                             _parse_hyperparams(args.hyperparams))
    report = run.report
    for name, score in sorted(report.per_space_score.items()):
        print(f"  {name:28s} {score:+.4f}")
    print(f"aggregate score (Eq. 3): {run.score:+.4f}  "
          f"[{args.strategy} x{args.repeats} repeats, "
          f"{len(report.per_space_score)} spaces]")
    print(f"simulated {run.simulated_seconds/3600:.2f} h of tuning in "
          f"{report.wall_seconds:.1f} s wall (drive: {run.fuse})")
    return 0


def cmd_hypertune(args) -> int:
    """Exhaustive hyperparameter tuning (paper Sec. IV-B, Table III)."""
    with tuner_from_args(args) as tuner:
        run = tuner.hypertune(args.strategy, journal=args.journal)
    res = run.hypertuning
    _print_ranking(res.results, args.top)
    best, avg = res.best, res.closest_to_mean()
    rel = (best.score - avg.score) / max(abs(avg.score), 1e-2)
    print(f"optimal vs average config: {best.score:+.4f} vs {avg.score:+.4f}"
          f" ({100*rel:+.1f}%; paper Sec. IV-B reports +94.8% on average)")
    print(f"campaign: {run.n_evaluated} configs, "
          f"{run.simulated_seconds/3600:.2f} simulated h replayed in "
          f"{run.wall_seconds:.1f} s wall ({args.workers} workers, "
          f"drive: {run.fuse})")
    if args.journal:
        print(f"journal: {args.journal}")
    return 0


def cmd_meta(args) -> int:
    """Meta-strategy hyperparameter tuning (paper Sec. IV-C, Eq. 4)."""
    with tuner_from_args(args) as tuner:
        run = tuner.meta(args.strategy, args.meta_strategy,
                         extended=not args.table3_grid,
                         max_hp_evals=args.max_hp_evals,
                         meta_hyperparams=_parse_hyperparams(
                             args.meta_hyperparams),
                         journal=args.journal)
    grid = hyperparam_searchspace(args.strategy,
                                  extended=not args.table3_grid)
    print(f"best hyperparameters for {args.strategy} "
          f"(found by {args.meta_strategy}): {run.best_hyperparams}")
    print(f"score {run.score:+.4f} after {run.n_evaluated} of "
          f"{grid.size} grid points ({run.wall_seconds:.1f} s wall"
          + (f", drive: {run.fuse}" if run.fuse else "") + ")")
    if run.speedup:
        print(f"simulated {run.simulated_seconds/3600:.2f} h of tuning "
              f"replayed in {run.wall_seconds:.1f} s wall "
              f"({run.speedup:,.0f}x)")
    if args.journal:
        print(f"journal: {args.journal}")
    return 0


def cmd_report(args) -> int:
    """Summarize a campaign journal (no recomputation)."""
    journal = CampaignJournal(args.journal)
    header, records = journal.read()
    if header is None:
        raise SystemExit(f"no journal at {args.journal}")
    mode = header.get("mode", "?")
    print(f"campaign: {mode} {header.get('strategy')} "
          f"(repeats={header.get('repeats')}, seed={header.get('seed')})")
    print(f"spaces: {', '.join(header.get('spaces', []))}")
    snapshots = [r for r in records if r.get("type") == "checkpoint"]
    records = [r for r in records if r.get("type") != "checkpoint"]
    if not records:
        print("no completed evaluations yet")
        return 0
    if mode == "exhaustive":
        results = {r["hp_id"]: HyperConfigResult(
            r["hyperparams"], report_from_json(r["report"]))
            for r in records}
        grid = hyperparam_searchspace(header["strategy"])
        print(f"progress: {len(results)}/{grid.size} configurations")
        _print_ranking(results, args.top)
        res = HyperTuningResult(header["strategy"], results, 0.0, 0.0)
        best, avg = res.best, res.closest_to_mean()
        rel = (best.score - avg.score) / max(abs(avg.score), 1e-2)
        print(f"optimal vs average config: {best.score:+.4f} vs "
              f"{avg.score:+.4f} ({100*rel:+.1f}%)")
        modes = {r.report.fuse for r in results.values()}
        print(f"drive: {modes.pop() if len(modes) == 1 else 'mixed'}")
        work = sum(r.report.wall_seconds for r in results.values())
    else:
        ranked = sorted(records, key=lambda r: -r["score"])[:args.top]
        for r in ranked:
            print(f"  {r['score']:+.4f}  {r['hp_id']}")
        if snapshots:
            print(f"mid-run state snapshots: {len(snapshots)} "
                  f"(resume continues inside the tuning run)")
        work = 0.0
    done_wall = max(r.get("done_wall", 0.0) for r in records)
    simulated = sum(r["report"]["simulated_seconds"] if "report" in r
                    else r["simulated_seconds"] for r in records)
    print(f"simulated tuning replayed: {simulated/3600:.2f} h")
    if done_wall:
        rate = 60.0 * len(records) / done_wall
        print(f"campaign wall: {done_wall:.1f} s "
              f"({rate:.1f} configs/min)")
        # simulated-vs-wall: the paper's Fig. 9 headline ratio, now
        # reported for meta campaigns too (MetaTuningResult carries
        # simulated_seconds since the api redesign)
        print(f"simulated-vs-wall speedup: {simulated/done_wall:,.0f}x")
    if work and done_wall:
        print(f"aggregate worker compute: {work:.1f} s -> "
              f"average parallelism {work/done_wall:.2f}x")
    return 0


def cmd_spaces(args) -> int:
    """Per-space stats (thin over ``repro.api.describe_space``)."""
    from .api import hyperparam_space_stats

    def row(st: dict) -> str:
        adj, ham = st["degrees"]["strictly_adjacent"], st["degrees"]["hamming"]
        return (f"  {st['name']:32s} {st['cartesian_size']:>9d} "
                f"{st['n_valid']:>8d} {st['valid_fraction']:>6.1%} "
                f"{adj['median']:>5.1f}/{adj['max']:<4d} "
                f"{ham['median']:>6.1f}/{ham['max']:<5d} "
                f"{st['compile_seconds']*1e3:>8.1f}")

    header = (f"  {'space':32s} {'cartesian':>9s} {'valid':>8s} {'frac':>6s} "
              f"{'adj med/max':>10s} {'ham med/max':>12s} {'compile ms':>9s}")
    tuner = tuner_from_args(args)
    print("search spaces (hub/cache selection):")
    print(header)
    for st in tuner.space_stats():
        print(row(st))
    print(f"hyperparameter grids "
          f"({'Table IV extended' if args.extended else 'Table III'}):")
    print(header)
    for st in hyperparam_space_stats(extended=args.extended):
        print(row(st))
    return 0


def _run_recording(args, bruteforce: bool) -> int:
    """``record``/``bruteforce``: fan one shard per worker out through the
    facade, which merges them into the output cache."""
    mode = "bruteforce" if bruteforce else "record"
    from .kernels import get_kernel
    try:
        get_kernel(args.kernel)  # fail fast on unknown kernels
    except KeyError as e:
        raise SystemExit(f"error: {e.args[0] if e.args else e}")
    tuner = Tuner(workers=args.workers, backend=args.backend, seed=args.seed,
                  progress=lambda msg: print(f"  {msg}", flush=True))
    with tuner:
        run = tuner.record(
            args.kernel, runner=args.runner, device=args.device,
            problem=_parse_hyperparams(getattr(args, "problem", None)),
            strategy=getattr(args, "strategy", "random_search"),
            hyperparams=_parse_hyperparams(
                getattr(args, "hyperparams", None)),
            repeats=args.repeats, max_evals=args.max_evals,
            max_seconds=args.seconds, out=args.out,
            bruteforce=bruteforce)
    cache = run.cache
    n_ok = cache.meta["n_ok"]
    total = (cache.space.size if cache.space is not None
             else len(cache.results))
    print(f"{mode}: {len(cache.results)}/{total} configs recorded "
          f"({n_ok} ok) for {args.kernel}@{cache.device} "
          f"[{args.runner}] in {run.wall_seconds:.1f} s wall "
          f"({max(1, args.workers)} workers)")
    if run.best_config is not None:
        print(f"best: {run.best_config} ({run.best_value*1e3:.3f} ms)")
    print(f"cache: {run.cache_path}")
    print(f"replay: python -m repro simulate --strategy random_search "
          f"--cache {run.cache_path}")
    return 0


def cmd_record(args) -> int:
    """Strategy-sampled recording of a registered kernel (the affordable
    way to turn a live space into simulation data)."""
    return _run_recording(args, bruteforce=False)


def cmd_bruteforce(args) -> int:
    """Exhaustive recording (paper Table II: brute-forcing the hub)."""
    return _run_recording(args, bruteforce=True)


def cmd_merge_cache(args) -> int:
    """Merge recording shards into one canonical cache file."""
    from .core import record as rec
    header, _ = rec.ObservationShard(args.shards[0]).read()
    if header is None:
        raise SystemExit(f"{args.shards[0]} has no shard header")
    space = rec.registry_space(header.get("kernel", ""),
                               header.get("problem"))
    cache = rec.merge_shards(args.shards, space=space)
    cache.save(args.out)
    print(f"merged {cache.meta['n_shards']} shards -> {args.out}: "
          f"{cache.meta['n_configs']} configs ({cache.meta['n_ok']} ok) "
          f"for {cache.kernel}@{cache.device}")
    if args.hub_root:
        from .api import Hub
        key = Hub(args.hub_root).register(
            cache, problem=header.get("problem") or None)
        print(f"registered in hub {args.hub_root} as {key} "
              f"(live lookup indexes invalidated)")
    return 0


def _lookup_hub(args):
    """A ``ConfigHub`` from the shared lookup/serve options."""
    from .service import ConfigHub
    warm: bool | dict = False
    if getattr(args, "warm_start", False):
        warm = {"max_evals": args.warm_max_evals}
    return ConfigHub(args.hub_root or _default_hub_root(),
                     verify=not args.no_verify,
                     ttl_s=getattr(args, "ttl", None), warm_start=warm)


def _default_hub_root() -> str:
    from .hub import DEFAULT_ROOT
    return DEFAULT_ROOT


def _print_lookup(r, as_json: bool) -> None:
    import json as _json
    if as_json:
        print(_json.dumps(r.to_json()))
        return
    print(f"{r.kernel}@{r.device} "
          f"{'{' + ', '.join(f'{k}={v}' for k, v in r.problem.items()) + '}'}"
          f": {r.status} (confidence {r.confidence:.2f})")
    if r.best_config is not None:
        val = (f"{r.best_value * 1e3:.3f} ms"
               if r.best_value not in (None, float('inf')) else "n/a")
        kind = "modeled" if r.status == "modeled" else "recorded ok"
        print(f"  best: {r.best_config} ({val}, over {r.n_configs} "
              f"{kind} configs)")
    if r.status == "transfer":
        print(f"  donor: {r.source} problem={r.donor_problem} "
              f"shape-distance {r.distance:.3f}")
    elif r.status == "modeled" and r.model:
        print(f"  model: {r.model['model']} on {r.model['device_model']} "
              f"({r.model['dominant']}-bound, "
              f"{r.model['n_ok']}/{r.model['n_valid']} configs feasible)")
    elif r.source:
        print(f"  source: {r.source}")
    print(f"  resolved in {r.wall_seconds * 1e6:.0f} us")


def cmd_lookup(args) -> int:
    """One-shot service lookup against the recorded hub."""
    hub = _lookup_hub(args)
    r = hub.lookup(args.kernel, _parse_hyperparams(args.problem) or None,
                   args.device)
    if args.wait and r.status == "warming" and hub.warm_start is not None:
        flight = hub.warm_start.ensure(args.kernel, args.device, r.problem)
        flight.join(args.wait)
        r = hub.lookup(args.kernel, _parse_hyperparams(args.problem) or None,
                       args.device)
    _print_lookup(r, args.json)
    return 0 if r.found else 3


def serve_requests(hub, lines) -> "object":
    """The ``serve`` loop, factored for tests: yields one result dict per
    input line. A line is a JSON object (one request: ``kernel`` plus
    optional ``problem``/``device``) or a JSON array of them (batched
    through ``lookup_many``). Bad lines yield an ``error`` dict instead of
    killing the service."""
    import json as _json
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            req = _json.loads(line)
            if isinstance(req, list):
                for r in hub.lookup_many(req):
                    yield r.to_json()
            else:
                yield hub.lookup(req["kernel"], req.get("problem"),
                                 req.get("device", "tpu_v5e")).to_json()
        except (ValueError, KeyError, TypeError) as e:
            yield {"error": f"{type(e).__name__}: {e}", "request": line}


def cmd_serve(args) -> int:
    """Stdin/stdout lookup service (one JSON request per line)."""
    import json as _json
    hub = _lookup_hub(args)
    if args.warm_up:
        n = hub.warm_up()
        print(f"warmed {n} hub entries", file=sys.stderr, flush=True)
    print(f"serving lookups over {hub.root} "
          f"(entries: {hub.stats()['entries']}); one JSON request per "
          f"line, e.g. {{\"kernel\": \"gemm\", \"device\": \"tpu_v5e\"}}",
          file=sys.stderr, flush=True)
    for result in serve_requests(hub, sys.stdin):
        print(_json.dumps(result), flush=True)
    stats = hub.stats()
    print(f"served {sum(stats['lookups'].values())} lookups "
          f"({stats['lookups']}); {stats['disk_loads']} cache loads",
          file=sys.stderr)
    return 0


def _build_matrix(args):
    """A ``ScenarioMatrix`` from the shared --kernels/--devices CSVs."""
    from .scenarios import ScenarioMatrix
    return ScenarioMatrix(
        kernels=args.kernels.split(",") if args.kernels else None,
        devices=args.devices.split(",") if args.devices else None)


def cmd_scenarios(args) -> int:
    """Coverage report over the scenario matrix: every (kernel x shape x
    device) triple with its tier, optionally best times and the recorded
    best-time regression gate (docs/scenarios.md)."""
    import json as _json

    from .scenarios import gate_recorded
    from .service import ConfigHub
    matrix = _build_matrix(args)
    hub = ConfigHub(args.hub_root or _default_hub_root(),
                    verify=not args.no_verify)
    with_best = args.best or bool(args.gate) or bool(args.out)
    report = matrix.coverage(hub, with_best=with_best)
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            _json.dump(report.to_json(), f, indent=1)
            f.write("\n")
    if args.json:
        print(_json.dumps(report.to_json(), indent=1))
    else:
        for row in report.rows:
            best = ""
            if row.best_value is not None:
                best = f"  {row.best_value * 1e3:.3f} ms"
            print(f"  {row.scenario.key:58s} {row.tier:8s}{best}")
        counts = report.counts()
        total = sum(counts.values())
        print(f"{total} scenarios: " + ", ".join(
            f"{counts.get(t, 0)} {t}" for t in ("recorded", "modeled",
                                                "cold")))
    if args.gate:
        with open(args.gate, "r", encoding="utf-8") as f:
            baseline = _json.load(f)
        base_best = {r["key"]: r["best_value"]
                     for r in baseline.get("rows", [])
                     if r.get("tier") == "recorded"
                     and r.get("best_value") is not None}
        failures = gate_recorded(report.recorded_best(), base_best,
                                 threshold=args.threshold)
        if failures:
            for msg in failures:
                print(f"  GATE {msg}")
            print(f"{len(failures)} recorded-best regression(s) vs "
                  f"{args.gate}")
            return 1
        print(f"gate ok: {len(base_best)} recorded baselines within "
              f"{args.threshold:.0%}")
    return 0


def cmd_fleet(args) -> int:
    """Run/resume the recording fleet: record -> merge -> register every
    runnable triple of the matrix into the hub, journaled so completed
    scenarios are skipped on re-run."""
    import json as _json

    from .scenarios import run_fleet
    outcome = run_fleet(
        args.hub_root or _default_hub_root(),
        matrix=_build_matrix(args),
        runner=args.runner, strategy=args.strategy,
        max_evals=args.max_evals, repeats=args.repeats,
        workers=args.workers, backend=args.backend, seed=args.seed,
        progress=_progress(args.quiet))
    if args.json:
        print(_json.dumps(outcome.to_json(), indent=1))
    else:
        print(f"fleet: {len(outcome.recorded)} recorded, "
              f"{len(outcome.skipped)} already journaled, "
              f"{len(outcome.covered)} already in hub, "
              f"{len(outcome.unrunnable)} unrunnable with "
              f"runner={args.runner}")
        for key in outcome.recorded:
            print(f"  recorded {key}")
    return 0


def cmd_hub(args) -> int:
    """Hub dataset management (build / info / verify / stats)."""
    import json as _json

    from .api import Hub
    hub = Hub(args.root)
    if args.action == "build":
        Hub.build(args.root)
        m = hub.manifest
        print(f"hub built at {os.path.abspath(hub.root)} in "
              f"{m['build_wall_seconds']:.1f}s wall")
        return 0
    if args.action == "verify":
        failures = hub.verify(strict=False)
        if failures:
            for key, reason in sorted(failures.items()):
                print(f"  FAIL {key}: {reason}")
            print(f"{len(failures)} of {hub.stats()['entries']} entries "
                  f"failed verification")
            return 1
        print(f"ok: all {hub.stats()['entries']} entries verified "
              f"(sha256)")
        return 0
    if args.action == "info":
        print(_json.dumps(hub.manifest, indent=1))
        return 0
    print(_json.dumps(hub.stats(), indent=1))  # stats
    return 0


DEFAULT_BASELINE = "parity-lint-baseline.json"


def cmd_lint(args) -> int:
    """parity-lint: the determinism/pickle-safety static-analysis gate
    (``repro.analysis``; rule catalogue in docs/static-analysis.md)."""
    import json as _json

    from .analysis import baseline as _baseline
    from .analysis import default_rules, lint_paths
    from .analysis.report import rule_catalogue, to_json, to_text

    rules = default_rules()
    if args.list_rules:
        print(rule_catalogue(rules))
        return 0
    paths = args.paths or ["src/repro"]
    for p in paths:
        if not os.path.exists(p):
            raise SystemExit(f"error: no such path: {p}")
    baseline_path = args.baseline
    if baseline_path is None and not args.no_baseline \
            and os.path.exists(DEFAULT_BASELINE):
        baseline_path = DEFAULT_BASELINE
    if args.no_baseline or args.write_baseline:
        baseline_path = None
    result = lint_paths(paths, baseline=baseline_path, rules=rules)
    if args.write_baseline:
        out = args.baseline or DEFAULT_BASELINE
        lines: dict = {}

        def line_text(f):
            if f.path not in lines:
                for root in paths:
                    cand = os.path.join(root, f.path)
                    if os.path.exists(cand):
                        with open(cand, "r", encoding="utf-8") as fh:
                            lines[f.path] = fh.read().splitlines()
                        break
                else:
                    lines[f.path] = []
            text = lines[f.path]
            return text[f.line - 1] if 1 <= f.line <= len(text) else ""

        n = _baseline.write(out, result.findings, line_text)
        print(f"wrote {n} baseline entr{'y' if n == 1 else 'ies'} "
              f"covering {len(result.findings)} finding(s) -> {out}")
        return 0
    if args.report:
        with open(args.report, "w", encoding="utf-8") as f:
            _json.dump(to_json(result, rules), f, indent=2)
            f.write("\n")
    if args.format == "json":
        print(_json.dumps(to_json(result, rules), indent=2))
    else:
        print(to_text(result))
    return 0 if result.ok else 1


def _print_ranking(results: dict, top: int) -> None:
    ranked = sorted(results.items(), key=lambda kv: -kv[1].score)
    for hp_id, r in ranked[:top]:
        print(f"  {r.score:+.4f}  {hp_id}")
    if len(ranked) > top:
        print(f"  ... {len(ranked) - top} more "
              f"(worst {ranked[-1][1].score:+.4f})")


# ------------------------------------------------------------------ parser
def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="repro",
        description="Tuning the Tuner — simulation-mode auto-tuning and "
                    "hyperparameter campaigns (parallel + resumable)")
    sub = p.add_subparsers(dest="command", required=True)

    ps = sub.add_parser("simulate", help="score one strategy configuration "
                        "with the methodology (Sec. III-B)")
    ps.add_argument("--strategy", required=True, choices=sorted(STRATEGIES))
    ps.add_argument("--hyperparams", default=None, metavar="K=V,...",
                    help="strategy hyperparameters (default: DEFAULTS)")
    _add_space_args(ps)
    _add_exec_args(ps)
    ps.set_defaults(fn=cmd_simulate)

    ph = sub.add_parser("hypertune", help="exhaustive hyperparameter "
                        "campaign (Table III), parallel + resumable")
    ph.add_argument("--strategy", required=True, choices=sorted(STRATEGIES))
    ph.add_argument("--journal", default=None, metavar="PATH",
                    help="JSONL checkpoint; rerun with the same path to "
                         "resume an interrupted campaign")
    ph.add_argument("--top", type=int, default=5,
                    help="show the N best configurations")
    ph.add_argument("--quiet", action="store_true")
    _add_space_args(ph)
    _add_exec_args(ph)
    ph.set_defaults(fn=cmd_hypertune)

    pm = sub.add_parser("meta", help="meta-strategy hyperparameter "
                        "optimization (Eq. 4, Table IV)")
    pm.add_argument("--strategy", required=True, choices=sorted(STRATEGIES))
    pm.add_argument("--meta-strategy", required=True,
                    choices=sorted(STRATEGIES))
    pm.add_argument("--max-hp-evals", type=int, default=50)
    pm.add_argument("--table3-grid", action="store_true",
                    help="search the small Table III grid instead of the "
                         "extended Table IV space")
    pm.add_argument("--meta-hyperparams", default=None, metavar="K=V,...")
    pm.add_argument("--journal", default=None, metavar="PATH")
    pm.add_argument("--quiet", action="store_true")
    _add_space_args(pm)
    _add_exec_args(pm)
    pm.set_defaults(fn=cmd_meta)

    pr = sub.add_parser("report", help="summarize a campaign journal")
    pr.add_argument("journal", metavar="JOURNAL",
                    help="path to a campaign JSONL journal")
    pr.add_argument("--top", type=int, default=10)
    pr.set_defaults(fn=cmd_report)

    psp = sub.add_parser("spaces", help="per-space stats: sizes, valid "
                         "fraction, neighbor degrees, compile time")
    psp.add_argument("--extended", action="store_true",
                     help="show the Table IV extended hyperparameter grids "
                          "instead of Table III")
    _add_space_args(psp)
    _add_exec_args(psp)
    psp.set_defaults(fn=cmd_spaces)

    def _add_record_args(pp, bruteforce: bool) -> None:
        pp.add_argument("--kernel", required=True,
                        help="registered kernel (gemm, convolution, "
                             "dedispersion, hotspot, flash_attention, ssd)")
        pp.add_argument("--runner", choices=("live", "costmodel",
                                             "surrogate"),
                        default=("costmodel" if bruteforce else "live"),
                        help="live = the Pallas kernel timed on the JAX "
                             "device (compiled on a TPU, interpret mode "
                             "elsewhere); costmodel = analytic device "
                             "model; surrogate = deterministic roofline "
                             "pricing (docs/scenarios.md)")
        pp.add_argument("--device", default=None,
                        help="device model for --runner costmodel/"
                             "surrogate (default tpu_v5e); a live "
                             "recording is labelled with the device it "
                             "runs on, and a --device naming another one "
                             "is an error")
        pp.add_argument("--problem", default=None, metavar="K=V,...",
                        help="problem-size overrides (e.g. m=256,n=256,"
                             "k=256); default: the kernel's smoke sizes")
        pp.add_argument("--repeats", type=int, default=3,
                        help="observations per fresh live evaluation")
        if not bruteforce:
            pp.add_argument("--strategy", default="random_search",
                            choices=sorted(STRATEGIES),
                            help="sampling strategy (default random_search)")
            pp.add_argument("--hyperparams", default=None, metavar="K=V,...")
        pp.add_argument("--max-evals", type=int,
                        default=(None if bruteforce else 64),
                        help="fresh-evaluation cap per worker"
                             + (" (default unlimited)" if bruteforce
                                else " (default 64)"))
        pp.add_argument("--seconds", type=float, default=None,
                        help="measured-seconds cap per worker")
        pp.add_argument("--out", default=None, metavar="PATH",
                        help="output cache (.json/.json.gz/.json.zst; "
                             "default recorded/<kernel>@<device>.json.gz). "
                             "Shards land next to it and survive crashes: "
                             "rerun the same command to resume.")
        pp.add_argument("--workers", type=int, default=1,
                        help="parallel recording workers (one shard each; "
                             "threads of this process for --runner live, "
                             "which run one after another on a TPU)")
        pp.add_argument("--backend", choices=("auto", "thread", "process"),
                        default="auto")
        pp.add_argument("--seed", type=int, default=0)

    prec = sub.add_parser("record", help="record a live/cost-model tuning "
                          "run of a registered kernel into a replayable "
                          "cache (strategy-sampled)")
    _add_record_args(prec, bruteforce=False)
    prec.set_defaults(fn=cmd_record)

    pbf = sub.add_parser("bruteforce", help="exhaustively record a "
                         "registered kernel's valid space (Table II)")
    _add_record_args(pbf, bruteforce=True)
    pbf.set_defaults(fn=cmd_bruteforce)

    pmc = sub.add_parser("merge-cache", help="merge recording shards into "
                         "one canonical T4 cache")
    pmc.add_argument("shards", nargs="+", metavar="SHARD",
                     help="shard JSONL files (from record/bruteforce)")
    pmc.add_argument("--out", required=True, metavar="PATH",
                     help="output cache path (.json/.json.gz/.json.zst)")
    pmc.add_argument("--hub-root", default=None, metavar="DIR",
                     help="also register the merged cache in this hub's "
                          "manifest and invalidate live lookup services")
    pmc.set_defaults(fn=cmd_merge_cache)

    def _add_lookup_args(pp, serve: bool) -> None:
        pp.add_argument("--hub-root", default=None, metavar="DIR",
                        help="hub directory (default: the bundled hub)")
        pp.add_argument("--no-verify", action="store_true",
                        help="skip sha256 verification when materializing "
                             "hub entries")
        pp.add_argument("--ttl", type=float, default=None, metavar="SECONDS",
                        help="re-stat materialized entries older than this "
                             "(default: only explicit invalidation)")
        pp.add_argument("--warm-start", action="store_true",
                        help="launch a journaled recording campaign "
                             "(single-flight) for cold keys")
        pp.add_argument("--warm-max-evals", type=int, default=32,
                        help="fresh-eval budget of a warm-start campaign")
        if not serve:
            pp.add_argument("--kernel", required=True,
                            help="kernel name (hub or registry)")
            pp.add_argument("--device", default="tpu_v5e")
            pp.add_argument("--problem", default=None, metavar="K=V,...",
                            help="problem sizes (default: the kernel's "
                                 "hub shape)")
            pp.add_argument("--json", action="store_true",
                            help="print the LookupResult as JSON")
            pp.add_argument("--wait", type=float, default=None,
                            metavar="SECONDS",
                            help="with --warm-start: block up to SECONDS "
                                 "for the campaign before answering")

    plk = sub.add_parser("lookup", help="best known config for (kernel, "
                         "problem, device) from the recorded hub")
    _add_lookup_args(plk, serve=False)
    plk.set_defaults(fn=cmd_lookup)

    psv = sub.add_parser("serve", help="lookup service: JSON requests on "
                         "stdin, LookupResult JSON lines on stdout")
    _add_lookup_args(psv, serve=True)
    psv.add_argument("--warm-up", action="store_true",
                     help="materialize every hub entry before serving")
    psv.set_defaults(fn=cmd_serve)

    psc = sub.add_parser("scenarios", help="coverage over the scenario "
                         "matrix: every (kernel x shape x device) triple, "
                         "recorded | modeled | cold")
    psc.add_argument("--kernels", default=None,
                     help="comma-separated kernels (default: all registered)")
    psc.add_argument("--devices", default=None,
                     help="comma-separated devices (default: hub devices "
                          "+ cpu_interpret)")
    psc.add_argument("--hub-root", default=None, metavar="DIR",
                     help="hub directory (default: the bundled hub)")
    psc.add_argument("--no-verify", action="store_true",
                     help="skip sha256 verification of hub entries")
    psc.add_argument("--best", action="store_true",
                     help="resolve and show the best time per triple")
    psc.add_argument("--json", action="store_true",
                     help="print the coverage report as JSON")
    psc.add_argument("--out", default=None, metavar="PATH",
                     help="also write the JSON report to PATH (the CI "
                          "artifact / gate baseline)")
    psc.add_argument("--gate", default=None, metavar="BASELINE",
                     help="fail if any recorded best time regressed vs "
                          "this earlier coverage JSON")
    psc.add_argument("--threshold", type=float, default=0.2,
                     help="allowed recorded-best slowdown for --gate "
                          "(default 0.2 = 20%%)")
    psc.set_defaults(fn=cmd_scenarios)

    pfl = sub.add_parser("fleet", help="run/resume the recording fleet "
                         "over the scenario matrix (journaled)")
    pfl.add_argument("--kernels", default=None,
                     help="comma-separated kernels (default: all registered)")
    pfl.add_argument("--devices", default=None,
                     help="comma-separated devices (default: hub devices "
                          "+ cpu_interpret)")
    pfl.add_argument("--hub-root", default=None, metavar="DIR",
                     help="hub directory to register into (default: the "
                          "bundled hub)")
    pfl.add_argument("--runner", choices=("live", "costmodel", "surrogate"),
                     default="costmodel",
                     help="recorder per triple (live runs cpu_interpret "
                          "scenarios only; default costmodel)")
    pfl.add_argument("--strategy", default="random_search",
                     choices=sorted(STRATEGIES))
    pfl.add_argument("--max-evals", type=int, default=64,
                     help="fresh-evaluation cap per scenario (default 64)")
    pfl.add_argument("--repeats", type=int, default=3,
                     help="observations per fresh evaluation (default 3)")
    pfl.add_argument("--workers", type=int, default=1)
    pfl.add_argument("--backend", choices=("auto", "thread", "process"),
                     default="auto")
    pfl.add_argument("--seed", type=int, default=0)
    pfl.add_argument("--json", action="store_true",
                     help="print the fleet outcome as JSON")
    pfl.add_argument("--quiet", action="store_true")
    pfl.set_defaults(fn=cmd_fleet)

    phub = sub.add_parser("hub", help="hub dataset management: build, "
                          "info, verify (sha256), stats")
    phub.add_argument("action", choices=("build", "info", "verify", "stats"))
    phub.add_argument("--root", default=None,
                      help="hub directory (default: the bundled hub)")
    phub.set_defaults(fn=cmd_hub)

    pl = sub.add_parser("lint", help="parity-lint: determinism & "
                        "pickle-safety static analysis (the CI gate)")
    pl.add_argument("paths", nargs="*", metavar="PATH",
                    help="files/directories to lint (default: src/repro)")
    pl.add_argument("--baseline", default=None, metavar="PATH",
                    help=f"baseline of grandfathered findings (default: "
                         f"{DEFAULT_BASELINE} when present)")
    pl.add_argument("--no-baseline", action="store_true",
                    help="ignore any baseline file: report everything")
    pl.add_argument("--write-baseline", action="store_true",
                    help="write the current findings as the new baseline "
                         "(to --baseline or the default path) and exit 0")
    pl.add_argument("--format", choices=("text", "json"), default="text",
                    help="stdout format (json is the machine-readable "
                         "report, incl. the rule catalogue)")
    pl.add_argument("--report", default=None, metavar="PATH",
                    help="also write the JSON report to PATH (the CI "
                         "artifact), regardless of --format")
    pl.add_argument("--list-rules", action="store_true",
                    help="print the rule catalogue (invariant + runtime "
                         "oracle per rule) and exit")
    pl.set_defaults(fn=cmd_lint)
    return p


# JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is not
# set: one fixed path inside the checkout, since the path is part of what a
# later run must find again
COMPILE_CACHE_DIR = os.path.normpath(
    os.path.join(os.path.dirname(__file__), "..", "..", ".jax_cache"))


def use_compile_cache() -> None:
    """Point JAX's persistent compilation cache at ``COMPILE_CACHE_DIR``,
    unless ``JAX_COMPILATION_CACHE_DIR`` is set (JAX then reads it
    itself). Call before the first compilation."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)


def main(argv: Sequence[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    use_compile_cache()
    try:
        return args.fn(args)
    except ValueError as e:
        # domain errors (journal mismatch, bad cache format, unknown
        # hyperparameters) are user errors, not crashes; this includes
        # json.JSONDecodeError (a ValueError) from malformed inputs
        raise SystemExit(f"error: {e}")
    except OSError as e:
        # missing/unreadable caches, journals, baselines, shard files:
        # one-line error, not a traceback
        raise SystemExit(f"error: {e}")


if __name__ == "__main__":
    sys.exit(main())
