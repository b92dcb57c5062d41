#!/usr/bin/env python3
"""Drive the tuner's two device paths once on one TPU chip and check them.

    python chip_smoke.py

Needs a TPU: anywhere else it exits non-zero before doing any work. It
runs in this one process (no worker processes: only one process may hold
the chip) through the same entry points a user calls, and writes its
caches under ``smoke/`` next to this file, which it empties first.

f64        the premise of the replay tables' design: float64 stored and
           added natively on the chip (which holds it as a pair of
           float32) against the int64 bit-pattern addition the replay
           scan uses. The counts of exact results are printed; the
           bit-pattern add and scan must be exact for every input.
campaign   the paper's Table III unit of work. ``bruteforce --runner
           costmodel`` builds the 12 train-split spaces (the 4 hub kernels
           x tpu_v5e, tpu_v4, tpu_lite_a at hub problem sizes); then
           ``Tuner.simulate`` scores genetic_algorithm x 25 repeats over
           them on the ``jax`` engine (fused campaigns, ``drive: device``)
           and on the ``scalar`` reference engine. The per-space scores
           must be bit-identical.
checksum   the scores behind ``BENCH_simulate.json``'s campaign checksum
           (``benchmarks/bench_simulate.py``, REPRO_FAST profile),
           recomputed on the ``jax`` engine: the hash must not move.
kernels    the other four registry kernels at hub sizes, compiled for the
           chip with one config each, checked against their references:
           the largest error must stay within ``tol`` of the reference's
           largest magnitude (float32 kernels 1e-4; ``ssd`` 2e-2, since
           its float32 matmuls run at the chip's default bf16 precision).
live       ``record --runner live`` of ``gemm`` at m=n=k=4096 (16
           evaluations, 3 repeats) and ``bruteforce --runner live`` of the
           whole ``flash_attention`` space at bh=32, bh_kv=8, seq=4096,
           d=128: Pallas kernels compiled for the chip, the caches labelled
           from ``device_kind``. The best recorded config of each is rerun
           and checked against ``gemm_ref`` / ``attention_ref``.

One line per phase, then, as the last line, the JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Any failed check exits non-zero without it.
"""
from __future__ import annotations

import contextlib
import hashlib
import inspect
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, "smoke")

REPEATS = 25                      # the methodology's repeats (Sec. III-B)
CAMPAIGN_STRATEGY = "genetic_algorithm"
GEMM_PROBLEM = {"m": 4096, "n": 4096, "k": 4096}
GEMM_EVALS = 16
ATTN_PROBLEM = {"bh": 32, "bh_kv": 8, "seq": 4096, "d": 128}
LIVE_REPEATS = 3
# the bench's campaign component (REPRO_FAST): its hyperparameter set and
# its spaces (gemm and hotspot on tpu_v5e plus a synthetic 512-config
# cache); the hash of its scores and its repeats are read from the
# committed BENCH_simulate.json
CHECKSUM_SET = (
    ("genetic_algorithm", {"popsize": 20, "maxiter": 100, "method": "uniform",
                           "mutation_chance": 10}),
    ("genetic_algorithm", {"popsize": 30, "maxiter": 50, "method": "two_point",
                           "mutation_chance": 20}),
    ("pso", {"popsize": 20, "maxiter": 100, "c1": 2.0, "c2": 1.0}),
    ("pso", {"popsize": 30, "maxiter": 50, "c1": 1.0, "c2": 0.5}),
    ("random_search", {}),
)


class Failed(SystemExit):
    def __init__(self, msg: str):
        super().__init__(f"chip_smoke: FAILED: {msg}")


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise Failed(msg)


class CompileClock:
    """Seconds XLA spent compiling while the clock runs (JAX's
    ``backend_compile_duration`` events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.EVENT:
            self.total += duration

    @contextlib.contextmanager
    def phase(self):
        """Yields ``[wall, compile]`` seconds, filled in on exit."""
        t0, c0, out = time.perf_counter(), self.total, [0.0, 0.0]
        yield out
        out[0], out[1] = time.perf_counter() - t0, self.total - c0


def cli(*argv: str) -> None:
    """``python -m repro <argv>`` in this process, its output to the log."""
    from repro import cli as repro_cli
    with open(os.path.join(WORK, "cli.log"), "a") as log, \
            contextlib.redirect_stdout(log):
        print("$ python -m repro " + " ".join(argv), flush=True)
        rc = repro_cli.main(list(argv))
    check(rc == 0, f"python -m repro {' '.join(argv)} exited {rc}")


def hub_problem(module) -> str:
    """A hub kernel's problem sizes: its ``space()`` defaults."""
    params = inspect.signature(module.space).parameters.values()
    return ",".join(f"{p.name}={p.default}" for p in params)


# ------------------------------------------------------------------ phases
def phase_f64() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.engine_jax.replay import as_f64, f64_add_bits, f64_bits

    def exact(got, want) -> int:
        return int((f64_bits(got) == f64_bits(want)).sum())

    rng = np.random.default_rng(0)
    n, m = 1 << 16, 4096
    a, b = rng.lognormal(-3, 2, n), rng.lognormal(-3, 2, n)
    idx = rng.integers(0, n, n)
    want_scan = np.cumsum(a[:m])

    def scan(add, x0, xs):
        return jax.lax.scan(lambda c, x: (add(c, x),) * 2, x0, xs)[1]
    with jax.enable_x64():
        da = jnp.asarray(a)
        native = {
            "round trip": exact(np.asarray(da), a),
            "gather": exact(np.asarray(jax.jit(lambda x, i: x[i])(
                da, jnp.asarray(idx))), a[idx]),
            "add": exact(np.asarray(jax.jit(jnp.add)(da, jnp.asarray(b))),
                         a + b)}
        native_scan = exact(np.asarray(jax.jit(
            lambda xs: scan(jnp.add, jnp.float64(0.0), xs))(da[:m])),
            want_scan)
        bits_add = exact(as_f64(jax.jit(f64_add_bits)(
            jnp.asarray(f64_bits(a)), jnp.asarray(f64_bits(b)))), a + b)
        bits_scan = exact(as_f64(jax.jit(
            lambda xs: scan(f64_add_bits, jnp.int64(0), xs))(
                jnp.asarray(f64_bits(a[:m])))), want_scan)
    print(f"f64: of {n} random doubles the chip keeps exact "
          + ", ".join(f"{k} {v}" for k, v in native.items())
          + f"; native left-to-right scan {native_scan}/{m} steps exact; "
          f"int64 bit-pattern add {bits_add}/{n}, scan {bits_scan}/{m}",
          flush=True)
    check(bits_add == n and bits_scan == m,
          "the int64 bit-pattern float64 addition is not exact on the chip")


def phase_campaign(clock: CompileClock) -> dict:
    from repro.api import Tuner
    from repro.core.devices import TRAIN_DEVICES
    from repro.kernels import HUB_KERNELS

    caches = {}
    with clock.phase() as build:
        for kernel, module in sorted(HUB_KERNELS.items()):
            for device in TRAIN_DEVICES:
                path = os.path.join(WORK, f"{kernel}@{device}.json.gz")
                cli("bruteforce", "--kernel", kernel, "--device", device,
                    "--problem", hub_problem(module), "--out", path)
                caches[kernel, device] = path
    check(len(caches) == 12, f"{len(caches)} train-split spaces, not 12")
    runs, times = {}, {}
    for engine in ("jax", "scalar"):
        with clock.phase() as t, \
                Tuner(caches=list(caches.values()), engine=engine,
                      repeats=REPEATS, seed=0) as tuner:
            runs[engine] = tuner.simulate(CAMPAIGN_STRATEGY)
        times[engine] = t
    jx, sc = runs["jax"], runs["scalar"]
    check(jx.fuse == "device", f"jax engine drove {jx.fuse!r}, not 'device'")
    same = [name for name, s in jx.report.per_space_score.items()
            if sc.report.per_space_score.get(name) == s]
    print(f"campaign: {CAMPAIGN_STRATEGY} x{REPEATS} over {len(caches)} "
          f"spaces; bruteforce {build[0]:.1f} s; jax {times['jax'][0]:.1f} s "
          f"wall ({times['jax'][1]:.1f} s compile, drive: {jx.fuse}); "
          f"scalar {times['scalar'][0]:.1f} s (drive: {sc.fuse}); "
          f"scores bit-identical on {len(same)}/{len(caches)} spaces; "
          f"aggregate {jx.score!r}", flush=True)
    check(len(same) == len(caches) and jx.score == sc.score,
          f"device scores differ from scalar on "
          f"{sorted(set(jx.report.per_space_score) - set(same))}")
    return caches


def _synthetic_cache(path: str, n: int = 512, seed: int = 7) -> str:
    """The bench's recorded-run-sized synthetic cache, failed configs
    included (``bench_simulate._small_cache``)."""
    import numpy as np

    from repro.core.cache import CachedResult, CacheFile
    from repro.core.searchspace import SearchSpace
    from repro.core.tunable import tunables_from_dict
    rng = np.random.default_rng(seed)
    space = SearchSpace(tunables_from_dict({"x": tuple(range(n // 8)),
                                            "y": tuple(range(8))}),
                        name=f"bench{n}")
    vals = rng.lognormal(mean=-6, sigma=0.8, size=n)
    fail = rng.random(n) < 0.05
    results = {}
    for i, cfg in enumerate(space.valid_configs):
        v = float(vals[i])
        results[space.config_id(cfg)] = (
            CachedResult("error", float("inf"), (), 0.4, 0.01) if fail[i]
            else CachedResult("ok", v, (v,) * 3, 0.3, 0.01))
    CacheFile(f"bench{n}", "synthetic", space, results).save(path)
    return path


def phase_checksum(clock: CompileClock, caches: dict) -> None:
    from repro.api import Tuner
    with open(os.path.join(ROOT, "BENCH_simulate.json")) as f:
        bench = json.load(f)["components"]["campaign"]
    want, repeats = bench["score_checksum"], bench["repeats"]
    paths = [caches["gemm", "tpu_v5e"], caches["hotspot", "tpu_v5e"],
             _synthetic_cache(os.path.join(WORK, "bench512.json.gz"))]
    scores, modes = {}, set()
    with clock.phase() as t, \
            Tuner(caches=paths, engine="jax", repeats=repeats,
                  seed=0) as tuner:
        for strategy, hp in CHECKSUM_SET:
            run = tuner.simulate(strategy, hp)
            hp_id = ",".join(f"{k}={hp[k]}" for k in sorted(hp))
            scores[f"{strategy}({hp_id})"] = run.score
            modes.add(run.fuse)
    digest = hashlib.sha256(json.dumps(
        {k: repr(v) for k, v in sorted(scores.items())},
        sort_keys=True).encode()).hexdigest()
    print(f"checksum: {len(CHECKSUM_SET)} strategy configs x{repeats} "
          f"over 3 spaces on the jax engine "
          f"(drive: {'/'.join(sorted(modes))}) in {t[0]:.1f} s "
          f"({t[1]:.1f} s compile): {digest[:16]}... "
          f"{'matches' if digest == want else 'DIFFERS from'} "
          f"{want[:8]}...", flush=True)
    check(modes == {"device"}, f"checksum campaigns drove {sorted(modes)}")
    check(digest == want, f"score checksum {digest} != {want}")


def _record_live(clock: CompileClock, kernel: str, problem: dict,
                 label: str, *extra: str):
    from repro.core.cache import CacheFile
    verb = "record" if extra else "bruteforce"
    path = os.path.join(WORK, f"{kernel}@live.json.gz")
    with clock.phase() as t:
        cli(verb, "--runner", "live", "--kernel", kernel, "--problem",
            ",".join(f"{k}={v}" for k, v in problem.items()),
            "--repeats", str(LIVE_REPEATS), "--out", path, *extra)
    cache = CacheFile.load(path)
    check(cache.device == label,
          f"{kernel} recorded as {cache.device!r}, not {label!r}")
    ok = {k: r for k, r in cache.results.items() if r.status == "ok"}
    check(bool(ok), f"no {kernel} config ran on the chip")
    key = min(ok, key=lambda k: ok[k].time_s)
    best = cache.space.as_dict(cache.space.config_from_id(key))
    return cache, ok, best, ok[key].time_s, t


def phase_kernels(clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import convolution as cv
    from repro.kernels import dedispersion as dd
    from repro.kernels import hotspot as hs
    from repro.kernels import ssd

    def rnd(seed, shape):
        return jax.random.normal(jax.random.PRNGKey(seed), shape,
                                 jnp.float32)

    def conv():
        x, f = rnd(1, (cv.HUB_H, cv.HUB_W)), rnd(2, (cv.HUB_FH, cv.HUB_FW))
        return (cv.conv2d(x, f, strip_h=16, block_w=256),
                cv.conv2d_ref(x, f))

    def stencil():
        t = rnd(3, (hs.HUB_H, hs.HUB_W))
        p = rnd(4, (hs.HUB_H, hs.HUB_W)) * 0.1
        return (hs.hotspot(t, p, strip_h=64, block_w=512, t_block=2),
                hs.hotspot_ref(t, p, t_block=2))

    def dedisperse():
        x = rnd(5, (dd.HUB_NCHAN, dd.HUB_NTIME))
        d = dd.make_delays(dd.HUB_NCHAN, dd.HUB_NDM)
        return (dd.dedisperse(x, d, block_dm=16, block_t=256),
                dd.dedisperse_ref(x, d))

    def scan():
        bh, seq, p, n = 192, 4096, 64, 64
        x = rnd(6, (bh, seq, p))
        b, c = rnd(9, (bh, seq, n)), rnd(10, (bh, seq, n))
        dt = jax.nn.softplus(rnd(7, (bh, seq))) * 0.1
        a = -jax.nn.softplus(rnd(8, (bh,)))
        with jax.default_matmul_precision("highest"):
            ref = ssd.ssd_ref(x, dt, a, b, c)
        return ssd.ssd_scan(x, dt, a, b, c, chunk=64), ref

    cases = (("convolution 4096^2 17x17, strip 16/256", conv, 1e-4),
             ("hotspot 4096^2, 64/512, t_block 2", stencil, 1e-4),
             ("dedispersion 256x16384, 256 DMs, 16/256", dedisperse, 1e-4),
             ("ssd bh 192, seq 4096, p=n=64, chunk 64", scan, 2e-2))
    for name, run, tol in cases:
        with clock.phase() as t:
            out, ref = (np.asarray(v, np.float32) for v in run())
        err, scale = float(np.max(np.abs(out - ref))), float(
            np.max(np.abs(ref)))
        within = bool(np.isfinite(out).all()) and err <= tol * scale
        print(f"kernel {name}: {t[0]:.1f} s wall ({t[1]:.1f} s compile); "
              f"max abs error vs ref {err:.4g}, ref max {scale:.4g} "
              f"({'within' if within else 'OUTSIDE'} {tol} of it)",
              flush=True)
        check(within, f"{name} differs from its reference by {err}")


def phase_live(clock: CompileClock) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.devices import live_device
    from repro.kernels import flash_attention as fa
    from repro.kernels import gemm as gm

    label, interpret = live_device()  # compiled on the chip
    how = "interpreted" if interpret else "compiled for the chip"

    cache, ok, best, best_s, t = _record_live(
        clock, "gemm", GEMM_PROBLEM, label, "--max-evals", str(GEMM_EVALS))
    check(len(cache.results) == GEMM_EVALS,
          f"gemm recorded {len(cache.results)} configs, not {GEMM_EVALS}")
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    m, n, k = GEMM_PROBLEM["m"], GEMM_PROBLEM["n"], GEMM_PROBLEM["k"]
    a = jax.random.normal(ks[0], (m, k), jnp.float32).astype(jnp.bfloat16)
    b = jax.random.normal(ks[1], (k, n), jnp.float32).astype(jnp.bfloat16)
    c0 = jax.random.normal(ks[2], (m, n), jnp.float32).astype(jnp.bfloat16)
    out = np.asarray(gm.gemm(a, b, c0, block_m=best["block_m"],
                             block_n=best["block_n"],
                             block_k=best["block_k"],
                             interpret=interpret), np.float32)
    ref = np.asarray(gm.gemm_ref(a, b, c0), np.float32)
    err = float(np.max(np.abs(out - ref)))
    # both accumulate bf16 products in float32 and round to bf16 once: they
    # may differ by one bf16 unit in the last place (2**-7 relative)
    within = bool(np.all(np.abs(out - ref) <= 2.0 ** -7 * (np.abs(ref) + 1)))
    print(f"live gemm@{label}: {len(cache.results)} configs {how}, "
          f"{len(ok)} ok / {len(cache.results) - len(ok)} error, "
          f"{t[0]:.1f} s wall ({t[1]:.1f} s compile); best {best} "
          f"{best_s * 1e3:.3f} ms; max abs error vs gemm_ref {err:.4g} "
          f"({'within' if within else 'OUTSIDE'} one bf16 ulp)", flush=True)
    check(within, f"gemm {best} differs from gemm_ref by {err}")

    cache, ok, best, best_s, t = _record_live(
        clock, "flash_attention", ATTN_PROBLEM, label)
    check(len(cache.results) == cache.space.size,
          f"flash_attention recorded {len(cache.results)} of "
          f"{cache.space.size} configs")
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    p = ATTN_PROBLEM
    q = jax.random.normal(ks[0], (p["bh"], p["seq"], p["d"]), jnp.float32)
    kk = jax.random.normal(ks[1], (p["bh_kv"], p["seq"], p["d"]), jnp.float32)
    v = jax.random.normal(ks[2], (p["bh_kv"], p["seq"], p["d"]), jnp.float32)
    out = fa.flash_attention(q, kk, v, block_q=best["block_q"],
                             block_kv=best["block_kv"], causal=True,
                             interpret=interpret)
    # the S x S reference on the first two KV groups (8 query heads) keeps
    # its logits to 0.5 GB; the GQA head mapping is unchanged
    h = 2 * p["bh"] // p["bh_kv"]
    with jax.default_matmul_precision("highest"):
        ref = fa.attention_ref(q[:h], kk[:2], v[:2], causal=True)
    out, ref = np.asarray(out[:h]), np.asarray(ref)
    err = float(np.max(np.abs(out - ref)))
    # the kernel's float32 matmuls run at the chip's default precision
    # (bf16 passes): the repo's bf16 attention tolerance applies
    tol = 2e-2
    within = bool(np.allclose(out, ref, rtol=tol, atol=tol))
    print(f"live flash_attention@{label}: {len(cache.results)} configs "
          f"{how}, {len(ok)} ok / "
          f"{len(cache.results) - len(ok)} error, {t[0]:.1f} s wall "
          f"({t[1]:.1f} s compile); best {best} {best_s * 1e3:.3f} ms; "
          f"max abs error vs attention_ref {err:.4g} "
          f"({'within' if within else 'OUTSIDE'} {tol})", flush=True)
    check(within, f"flash_attention {best} differs from attention_ref "
                  f"by {err}")


def main() -> int:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.cli import use_compile_cache
    use_compile_cache()
    # recordings resume from shards they find: start from none
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    clock = CompileClock()
    phase_f64()
    caches = phase_campaign(clock)
    phase_checksum(clock, caches)
    phase_kernels(clock)
    phase_live(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
