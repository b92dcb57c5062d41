"""Benchmark-regression gate: compare a fresh bench report to the baseline.

Usage:
    PYTHONPATH=src python -m benchmarks.check_regression \
        --baseline BENCH_simulate.json --current /tmp/bench.json \
        [--threshold 0.20]

Two checks, both designed to transfer across runner hardware:

  1. **Score checksum** — the campaign component's scores are bit-exact
     functions of the code (engine parity is asserted inside the bench
     itself); the checksum must equal the committed baseline's whenever the
     profiles match. A mismatch means a PR changed simulation *results*,
     not just speed — that must be an intentional, reviewed change.
  2. **Throughput** — per-component *normalized* speedup (vectorized vs
     scalar wall on the same host, same process) must not drop more than
     ``--threshold`` (default 20 %) below the baseline's. Absolute
     evals/sec depends on the runner's silicon; the vectorized/scalar
     ratio does not, so the committed baseline stays meaningful on any
     machine. A drop means the vectorized engine lost ground against the
     scalar reference — i.e. someone slowed the hot path down.

To bump the baseline intentionally (engine change, profile change), rerun
``python -m benchmarks.run bench --json BENCH_simulate.json`` and commit
the result — see docs/performance.md.

Exit code 0 = pass, 1 = regression, 2 = unusable input.
"""
from __future__ import annotations

import argparse
import json
import sys

# the vectorized engine must never be materially slower than the scalar
# reference, whatever the committed baseline says (0.9, not 1.0, to absorb
# shared-runner timing noise on near-1x components)
MIN_SPEEDUP = 0.9

# per-component hard floors on top of the relative threshold: claims the
# repo makes about itself that must hold on any runner, not just relative
# to the committed baseline. drive_many's fused resolution of the
# methodology grid is ≥2x over the scalar reference by design (the
# committed baseline shows ~2.2x); the floor sits ~10% under the claim to
# absorb shared-runner timing noise — a drop below means the fused driver
# path genuinely regressed. local_search pins the compiled-space claim:
# whole-neighborhood row replay is ≥2x over the scalar per-evaluation
# reference. space_compile pins the compiled enumeration/CSR construction
# itself, which is an order of magnitude faster than the scalar lazy
# build (committed baseline ~20x; the floor leaves room for slower
# constraint-bound hosts).
# jax_replay pins the jitted engine's headline claim: fused fresh-replay
# through one vmapped device dispatch is ≥10x the numpy engine's chunked
# row commits on the same workload (committed baseline shows well above;
# the hard floor *is* the claim — see docs/performance.md).
# hub_lookup pins the ConfigHub service claim: a warmed exact hit (dict
# probe of a precomputed best) is ≥20x the naive in-memory scan a caller
# without the service pays per request (committed baseline ~35x; the floor
# leaves room for hosts where the scalar scan is relatively faster).
# surrogate pins the modeled tier's caching claim: a warmed modeled
# lookup (the cached roofline argmin) is ≥5x re-pricing the kernel's
# whole valid space per call (committed baseline ~10x on the 50-config
# flash-attention space; the margin absorbs hosts where pure-Python
# pricing is relatively faster).
# fused_campaign pins the device-resident campaign claim: whole
# random-search campaigns through drive_fused (vmapped replay dispatches
# + array-native improvement extraction, materialize=False) are ≥10x the
# scalar per-evaluation campaign loop — the hard floor *is* the claim
# (committed baseline ~14x, >1M fresh evals/s on CPU; see
# docs/performance.md "host↔device round-trip budget").
COMPONENT_MIN = {"drive_many": 1.8, "local_search": 2.0,
                 "space_compile": 5.0, "jax_replay": 10.0,
                 "hub_lookup": 20.0, "surrogate": 5.0,
                 "fused_campaign": 10.0}


def _unusable(msg: str) -> SystemExit:
    print(msg, file=sys.stderr)
    return SystemExit(2)


def load(path: str) -> dict:
    try:
        with open(path) as f:
            d = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise _unusable(f"cannot read bench report {path}: {e}")
    if d.get("format") != "repro-bench-simulate":
        raise _unusable(f"{path} is not a repro-bench-simulate report")
    return d


def compare(baseline: dict, current: dict, threshold: float) -> list[str]:
    """Return a list of human-readable failures (empty = gate passes)."""
    failures: list[str] = []
    if baseline.get("version") != current.get("version"):
        failures.append(
            f"bench schema version changed "
            f"({baseline.get('version')} -> {current.get('version')}); "
            "regenerate and commit the baseline")
        return failures
    if baseline.get("profile") != current.get("profile"):
        failures.append(
            "bench profile differs from the baseline's "
            f"({baseline.get('profile')} vs {current.get('profile')}); "
            "regenerate and commit the baseline")
        return failures
    if baseline["score_checksum"] != current["score_checksum"]:
        failures.append(
            "score checksum mismatch: simulation results changed "
            f"({baseline['score_checksum'][:16]}… -> "
            f"{current['score_checksum'][:16]}…). If intentional, "
            "regenerate BENCH_simulate.json and commit it with the change.")
    for name, base_c in baseline["components"].items():
        cur_c = current["components"].get(name)
        if cur_c is None:
            failures.append(f"component {name!r} missing from current run")
            continue
        # relative floor, but never below MIN_SPEEDUP (or the component's
        # own hard floor): for components whose baseline ratio is close to
        # 1x (campaign), a purely relative tolerance would wave through a
        # vectorized engine that has become outright slower than the
        # scalar reference
        floor = max(base_c["speedup"] * (1.0 - threshold),
                    COMPONENT_MIN.get(name, MIN_SPEEDUP))
        if cur_c["speedup"] < floor:
            failures.append(
                f"{name}: engine speedup regressed "
                f"{base_c['speedup']:.2f}x -> {cur_c['speedup']:.2f}x "
                f"(allowed floor {floor:.2f}x at {threshold:.0%} tolerance, "
                f"hard minimum {MIN_SPEEDUP}x)")
    return failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--baseline", required=True,
                    help="committed BENCH_simulate.json")
    ap.add_argument("--current", required=True,
                    help="report from this run")
    ap.add_argument("--threshold", type=float, default=0.20,
                    help="allowed fractional speedup regression "
                         "(default 0.20)")
    args = ap.parse_args(argv)
    baseline = load(args.baseline)
    current = load(args.current)
    failures = compare(baseline, current, args.threshold)
    for name in baseline["components"]:
        b = baseline["components"][name]
        c = current["components"].get(name, {})
        print(f"  {name:16s} speedup {b.get('speedup', float('nan')):6.2f}x -> "
              f"{c.get('speedup', float('nan')):6.2f}x")
    if failures:
        print("\nBENCH GATE FAILED:", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
        return 1
    print(f"\nbench gate OK (checksum {current['score_checksum'][:16]}…, "
          f"geomean speedup {current.get('speedup_geomean', 0):.2f}x)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
