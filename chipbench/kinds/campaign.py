"""The campaign generator: a hypertuning user scoring strategy
configurations with ``Tuner.simulate`` over recorded search spaces.

Configuration (``configs/<name>.json``, ``"kind": "campaign"``):

  spaces    ``{"devices": [...], "problems": {kernel: {size: value}}}``:
            each kernel x device space is built once per checkout with the
            program's ``costmodel`` runner (bruteforce, seed 0) under
            ``work/spaces/``, keyed by a hash of kernel, device and
            problem; later runs load it
  repeats, cutoff, engine   as ``Tuner`` takes them

Traffic (``traffic/<name>.json``):

  strategy     the strategy whose configurations are scored
  hyperparams  the cycle: the configurations a pass scores, in order,
               the same for every ``--seed``
  grid         the strategy's whole grid, ``{name: [values]}``, which
               the cycle is drawn from (read by the tests, not the run)

A step of the window scores the whole cycle, so the window ends on a
cycle's end and its rate weighs every configuration alike. Pass ``p`` of
the window scores with the ``Tuner`` seed ``--seed + 1 + p``, so no
(configuration, seed) pair is scored twice in a run; set-up scores at
seeds the window never reaches (``WARM_PASSES``, ``PRIME_PASSES``). After
the window the plain reference rescores, for each configuration of the
cycle, one of its completed passes drawn from ``--seed``, and the two are
compared exactly.
"""
from __future__ import annotations

import contextlib
import gzip
import hashlib
import io
import json
import os
import random
import sys
import time

import harness

RATE = "hp_configs_per_s"
SPAN = "chipbench.simulate"
# A pass at a new seed meets padded replay shapes (runs x segment length,
# powers of two, per space) that earlier passes did not: on the CPU backend
# 35 shapes in a first pass, 37 after two, 41 after 20 seeds and none new
# in the last 8. Set-up scores WARM_PASSES passes, at seeds no window
# reaches; where they compiled more programs than they loaded from the
# persistent cache (a checkout's first run), PRIME_PASSES more, to put the
# shapes other seeds meet in the cache.
WARM_PASSES = 2
PRIME_PASSES = 12
WARM_SEEDS = 10 ** 6


def _space_key(kernel: str, device: str, problem: dict) -> str:
    blob = json.dumps([kernel, device, problem], sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def payload_digest(paths) -> str:
    """sha256 over the decompressed contents of the space files."""
    h = hashlib.sha256()
    for p in paths:
        with open(p, "rb") as f:
            raw = f.read()
        h.update(gzip.decompress(raw) if raw[:2] == b"\x1f\x8b" else raw)
    return h.hexdigest()


def build_spaces(config: dict, work: str, log) -> list:
    """The configuration's recorded spaces, built where missing; paths in
    kernel-major order."""
    from repro.api import Tuner
    spaces = config["spaces"]
    paths, built = [], 0
    for kernel in sorted(spaces["problems"]):
        problem = spaces["problems"][kernel]
        for device in spaces["devices"]:
            path = os.path.join(work, "spaces", _space_key(
                kernel, device, problem) + ".json.gz")
            if not os.path.exists(path):
                with contextlib.redirect_stdout(io.StringIO()), \
                        Tuner(seed=0) as tuner:
                    tuner.record(kernel, runner="costmodel", device=device,
                                 problem=problem, repeats=3, max_evals=None,
                                 out=path, bruteforce=True)
                shard = path[:-len(".json.gz")] + ".shard-00.jsonl"
                if os.path.exists(shard):
                    os.remove(shard)
                built += 1
            paths.append(path)
    print(f"spaces: {len(paths)} ({built} built now), sha256 "
          f"{payload_digest(paths)}", file=log, flush=True)
    return paths


def hp_label(strategy: str, hp: dict) -> str:
    return f"{strategy}(" + ",".join(f"{k}={hp[k]}" for k in sorted(hp)) \
        + ")"


class Generator:
    rate = RATE

    def __init__(self, cell, seed: int, log=sys.stdout):
        self.cell = cell
        self.config = cell.config
        self.seed = seed
        self.log = log
        self.strategy = cell.traffic["strategy"]
        self.cycle = [dict(hp) for hp in cell.traffic["hyperparams"]]
        self.passes = 0
        self.done: list = []   # (pass, cycle index, result)
        self.tuner = None
        self.paths: list = []

    def pass_seed(self, p: int) -> int:
        return self.seed + 1 + p

    def _score_cycle(self, seed: int) -> None:
        self.tuner.seed = seed
        for hp in self.cycle:
            self.tuner.simulate(self.strategy, hp)

    # ---------------------------------------------------------------- set-up
    def setup(self) -> None:
        from repro.api import Tuner
        self.paths = build_spaces(self.config, harness.WORK, self.log)
        self.tuner = Tuner(caches=self.paths, engine=self.config["engine"],
                           repeats=int(self.config["repeats"]),
                           cutoff=float(self.config["cutoff"]),
                           seed=self.seed)
        self.tuner.scorers  # loads the spaces and builds the baselines
        clock = harness.CompileClock()
        with clock.phase() as warm:
            for k in range(WARM_PASSES):
                self._score_cycle(self.seed + WARM_SEEDS + k)
        if warm[2] - warm[3] > warm[3]:
            # the persistent cache was cold: fill it with the padded
            # replay shapes that other seeds meet, so that a later run's
            # window loads them and a compile there stays rare
            for k in range(WARM_PASSES, WARM_PASSES + PRIME_PASSES):
                self._score_cycle(self.seed + WARM_SEEDS + k)
            print(f"setup: the warm-up compiled {warm[2] - warm[3]} "
                  f"programs; {PRIME_PASSES} more passes primed the "
                  f"persistent cache", file=self.log, flush=True)

    # ---------------------------------------------------------------- window
    def step(self) -> int:
        """Score the whole cycle at this pass's seed; returns the
        configurations scored."""
        import jax
        self.tuner.seed = self.pass_seed(self.passes)
        for i, hp in enumerate(self.cycle):
            with jax.profiler.TraceAnnotation(
                    f"{SPAN} {hp_label(self.strategy, hp)}"):
                rep = self.tuner.simulate(self.strategy, hp).report
            self.done.append((self.passes, i, {
                "score": rep.score,
                "per_space": dict(rep.per_space_score),
                "simulated_seconds": rep.simulated_seconds,
                "fresh_evals": rep.fresh_evals}))
        self.passes += 1
        return len(self.cycle)

    def close(self) -> None:
        if self.tuner is not None:
            self.tuner.close()
            self.tuner = None

    # ----------------------------------------------------------------- check
    def check(self, control=None) -> tuple:
        """Rescore with the plain reference, for each configuration of the
        cycle, one completed pass drawn from the seed, and compare every
        number exactly: the aggregate and per-space scores, the simulated
        seconds the runs spent and their fresh evaluations. ``control``
        (any value) puts the reference with float32 budget accumulation in
        the program's place. Returns ``(correct, compared, attempted,
        failed)``."""
        from reference import campaign as ref
        self.close()
        by_cfg: dict = {}
        for p, i, res in self.done:
            by_cfg.setdefault(i, []).append((p, res))
        rng = random.Random(self.seed)
        picked = {i: rng.choice(by_cfg[i]) for i in sorted(by_cfg)}
        t0 = time.perf_counter()
        scorers = [ref.Scorer(ref.Space(p), float(self.config["cutoff"]))
                   for p in self.paths]
        repeats = int(self.config["repeats"])
        gaps = {"score_max_abs_gap": 0.0, "simulated_s_max_abs_gap": 0.0,
                "fresh_evals_gap": 0}
        unequal = 0
        for i, (p, got) in picked.items():
            hp, seed = self.cycle[i], self.pass_seed(p)
            want = ref.score(scorers, self.strategy, hp, repeats, seed)
            if control:
                got = ref.score(scorers, self.strategy, hp, repeats, seed,
                                accum="float32")
            score = [abs(got["score"] - want["score"])]
            if set(got["per_space"]) != set(want["per_space"]):
                score.append(float("inf"))
            else:
                score += [abs(got["per_space"][n] - want["per_space"][n])
                          for n in want["per_space"]]
            this = {"score_max_abs_gap": max(score),
                    "simulated_s_max_abs_gap": abs(
                        got["simulated_seconds"] - want["simulated_seconds"]),
                    "fresh_evals_gap": abs(got["fresh_evals"]
                                           - want["fresh_evals"])}
            unequal += any(v != 0 for v in this.values())
            for key, v in this.items():
                gaps[key] = max(gaps[key], v)
            print(f"check: {hp_label(self.strategy, hp)} pass {p} seed "
                  f"{seed}: reference {want['score']!r} "
                  f"{want['simulated_seconds']!r} s {want['fresh_evals']} "
                  f"evals; program {got['score']!r} "
                  f"{got['simulated_seconds']!r} s {got['fresh_evals']} "
                  f"evals", file=self.log, flush=True)
        print(f"check: {len(picked)} of {len(self.done)} completions in "
              f"{time.perf_counter() - t0:.1f} s", file=self.log, flush=True)
        compared = [(key, v, 0) for key, v in gaps.items()]
        correct = bool(picked) and not any(v != 0 for v in gaps.values())
        return correct, compared, len(self.done), unequal
