"""Plain reference for a Table III campaign: the score of one strategy
configuration over recorded search spaces (arXiv:2509.26300 Sec. III-B).

Written from the paper's method and the recorded-cache format alone; it
imports nothing of the program under test. Every step is the plain,
one-evaluation-at-a-time form:

  * a space is its tunables and the set of recorded config ids (a config
    is valid exactly when it was recorded), enumerated in product order;
  * a tuning run asks configs one by one; a fresh config charges its
    recorded compile + run + overhead seconds, a revisit is free, and a
    fresh config asked once the spent seconds reach the budget ends the
    run;
  * the baseline is 1000 virtual random-search runs (sampling without
    replacement); the budget is the time at which it reaches the cutoff
    fraction of the median-to-optimum distance; a run's score P_t (Eq. 2)
    is sampled at 50 equidistant times and averaged over repeats and
    spaces (Eq. 3).

Scores are float64 and compared exactly: the program states bit-identical
scores across its engines. ``accum`` is the precision of the budget
accumulation; ``"float32"`` is the control, which has to fail that
comparison.
"""
from __future__ import annotations

import gzip
import json
import math
import random
import zlib

import numpy as np

BASELINE_RUNS = 1000
BASELINE_SEED = 0xB0B
HARD_TIME_CAP_EVALS = 3000
N_SAMPLES = 50
FAILURE_FITNESS = 1e12
INF = float("inf")


class Exhausted(Exception):
    pass


class Space:
    """A recorded search space read straight from its T4-mini JSON file."""

    def __init__(self, path: str):
        with open(path, "rb") as f:
            raw = f.read()
        if raw[:2] == b"\x1f\x8b":
            raw = gzip.decompress(raw)
        d = json.loads(raw)
        self.name = f"{d['kernel']}@{d['device']}"
        self.values = [tuple(v) for v in d["tunables"].values()]
        self.results = {}
        for key, r in d["results"].items():
            value = INF if r["time_s"] is None else r["time_s"]
            charge = r["compile_s"] + sum(r["times_s"]) \
                + r.get("overhead_s", 0.0)
            self.results[key] = (value, charge)
        self.charges = [c for _, c in self.results.values()]
        self.mean_eval_charge = sum(self.charges) / len(self.charges)
        self._valid = None
        self._nbrs = {}
        self._repair = {}

    @staticmethod
    def key(config: tuple) -> str:
        return ",".join(str(v) for v in config)

    def is_valid(self, config: tuple) -> bool:
        return self.key(config) in self.results

    def valid(self) -> list:
        if self._valid is None:
            out = []

            def rec(i, prefix):
                if i == len(self.values):
                    if self.is_valid(prefix):
                        out.append(prefix)
                    return
                for v in self.values[i]:
                    rec(i + 1, prefix + (v,))
            rec(0, ())
            self._valid = out
        return self._valid

    def random_config(self, rng: random.Random) -> tuple:
        for _ in range(64):
            c = tuple(rng.choice(vals) for vals in self.values)
            if self.is_valid(c):
                return c
        valid = self.valid()
        return valid[rng.randrange(len(valid))]

    def neighbors(self, config: tuple) -> list:
        """Every valid config that differs in one tunable, tunable by
        tunable, nearest value first."""
        hit = self._nbrs.get(config)
        if hit is None:
            hit = []
            for i, vals in enumerate(self.values):
                j = vals.index(config[i])
                for k in sorted((k for k in range(len(vals)) if k != j),
                                key=lambda k: abs(k - j)):
                    c = config[:i] + (vals[k],) + config[i + 1:]
                    if self.is_valid(c):
                        hit.append(c)
            self._nbrs[config] = hit
        return hit

    def nearest_valid(self, config: tuple, rng: random.Random) -> tuple:
        """Breadth-first over single-tunable moves, three levels deep, then
        a random valid config."""
        if self.is_valid(config):
            return config
        hit = self._repair.get(config)
        if hit is not None:
            return hit
        frontier, seen = [config], {config}
        for _depth in range(3):
            nxt = []
            for c in frontier:
                for i, vals in enumerate(self.values):
                    j = vals.index(c[i])
                    for k in sorted(range(len(vals)),
                                    key=lambda k: abs(k - j)):
                        cc = c[:i] + (vals[k],) + c[i + 1:]
                        if cc in seen:
                            continue
                        seen.add(cc)
                        if self.is_valid(cc):
                            self._repair[config] = cc
                            return cc
                        nxt.append(cc)
            frontier = nxt[:256]
        return self.random_config(rng)


class Run:
    """One tuning run's evaluations against a simulated-time budget."""

    def __init__(self, space: Space, max_s: float, accum: str):
        self.space = space
        self.max_s = max_s
        self.f32 = accum == "float32"
        self.spent = np.float32(0.0) if self.f32 else 0.0
        self.memo = {}
        self.trace = []  # (spent after the evaluation, value) per fresh one

    def evaluate(self, config: tuple) -> float:
        key = Space.key(config)
        hit = self.memo.get(key)
        if hit is not None:
            return hit
        if self.spent >= self.max_s:
            raise Exhausted
        value, charge = self.space.results[key]
        if self.f32:
            self.spent = np.float32(self.spent + np.float32(charge))
            self.trace.append((float(self.spent), value))
        else:
            self.spent += charge
            self.trace.append((self.spent, value))
        self.memo[key] = value
        return value


def fitness(value: float) -> float:
    return FAILURE_FITNESS if value == INF else value


# ------------------------------------------------------------- strategies
def _single_point(a, b, rng):
    if len(a) < 2:
        return a, b
    p = rng.randrange(1, len(a))
    return a[:p] + b[p:], b[:p] + a[p:]


def _two_point(a, b, rng):
    if len(a) < 3:
        return _single_point(a, b, rng)
    p, q = sorted(rng.sample(range(1, len(a)), 2))
    return a[:p] + b[p:q] + a[q:], b[:p] + a[p:q] + b[q:]


def _uniform(a, b, rng):
    c1, c2 = list(a), list(b)
    for i in range(len(a)):
        if rng.random() < 0.5:
            c1[i], c2[i] = c2[i], c1[i]
    return tuple(c1), tuple(c2)


def _disruptive_uniform(a, b, rng):
    diff = [i for i in range(len(a)) if a[i] != b[i]]
    rng.shuffle(diff)
    k = max((len(diff) + 1) // 2, min(1, len(diff)))
    c1, c2 = list(a), list(b)
    for i in diff[:k]:
        c1[i], c2[i] = c2[i], c1[i]
    return tuple(c1), tuple(c2)


CROSSOVERS = {"single_point": _single_point, "two_point": _two_point,
              "uniform": _uniform, "disruptive_uniform": _disruptive_uniform}


def genetic_algorithm(run: Run, rng: random.Random, method: str,
                      popsize: int, maxiter: int,
                      mutation_chance: int) -> None:
    """Rank-weighted parents, crossover, per-gene mutation with chance
    1/mutation_chance, repair to the nearest valid config, the best kept;
    a fresh random population after every ``maxiter`` generations."""
    space = run.space
    crossover = CROSSOVERS[method]
    p_mut = 1.0 / float(mutation_chance)
    weights = list(range(popsize, 0, -1))
    pop = None
    gen = 0
    while True:
        if pop is None:
            pop = [space.random_config(rng) for _ in range(popsize)]
            gen = 0
        values = [run.evaluate(c) for c in pop]
        ranked = [c for _, _, c in sorted(
            ((fitness(v), i, c) for i, (v, c) in enumerate(zip(values, pop))),
            key=lambda t: (t[0], t[1]))]
        children = [ranked[0]]
        while len(children) < popsize:
            a, b = rng.choices(ranked, weights=weights, k=2)
            for child in crossover(a, b, rng):
                out = list(child)
                for i, vals in enumerate(space.values):
                    if rng.random() < p_mut:
                        out[i] = vals[rng.randrange(len(vals))]
                children.append(space.nearest_valid(tuple(out), rng))
                if len(children) >= popsize:
                    break
        gen += 1
        pop = None if gen >= maxiter else children


def simulated_annealing(run: Run, rng: random.Random, T: float,
                        T_min: float, alpha: float, maxiter: int) -> None:
    """Walk the neighbor graph; accept a worse neighbor with probability
    exp(-relative loss / T); cool geometrically; restart at T_min."""
    space = run.space
    T0, T_min, alpha, maxiter = float(T), float(T_min), float(alpha), \
        int(maxiter)
    while True:
        current = space.random_config(rng)
        f_cur = fitness(run.evaluate(current))
        temp = T0
        while temp > T_min:
            for _ in range(maxiter):
                nbrs = space.neighbors(current)
                if not nbrs:
                    current = space.random_config(rng)
                    f_cur = fitness(run.evaluate(current))
                    continue
                cand = nbrs[rng.randrange(len(nbrs))]
                f_new = fitness(run.evaluate(cand))
                d_rel = (f_new - f_cur) / max(abs(f_cur), 1e-30)
                if d_rel <= 0 or rng.random() < math.exp(
                        -d_rel / max(temp, 1e-9)):
                    current, f_cur = cand, f_new
            temp *= alpha


STRATEGIES = {"genetic_algorithm": genetic_algorithm,
              "simulated_annealing": simulated_annealing}


# ---------------------------------------------------------------- scoring
class Scorer:
    """Baseline, budget and sample times of one space."""

    def __init__(self, space: Space, cutoff: float):
        self.space = space
        vals = np.array([v for v, _ in space.results.values()])
        charges = np.array(space.charges)
        self.values = np.sort(vals[np.isfinite(vals)])
        self.optimum = float(self.values[0])
        median = float(np.median(self.values))
        self._virtual_runs(vals, charges)
        target = median - cutoff * (median - self.optimum)
        lo, hi = float(charges.min()), float(HARD_TIME_CAP_EVALS
                                             * float(charges.mean()))
        if self.baseline(np.array([hi]))[0] <= target:
            for _ in range(48):
                mid = 0.5 * (lo + hi)
                if self.baseline(np.array([mid]))[0] <= target:
                    hi = mid
                else:
                    lo = mid
        self.budget = hi
        self.times = np.linspace(hi / N_SAMPLES, hi, N_SAMPLES)
        self.base_at = self.baseline(self.times)

    def _virtual_runs(self, values, charges) -> None:
        rng = np.random.default_rng(
            BASELINE_SEED ^ zlib.crc32(self.space.name.encode()))
        worst = values[np.isfinite(values)].max()
        ts, bs = [], []
        for _ in range(BASELINE_RUNS):
            perm = rng.permutation(len(values))
            v = values[perm]
            t = np.cumsum(charges[perm])
            run_min = np.fmin.accumulate(np.where(np.isfinite(v), v, np.inf))
            imp = np.ones(len(values), bool)
            imp[1:] = run_min[1:] < run_min[:-1]
            imp &= np.isfinite(run_min)
            ts.append(t[imp])
            bs.append(run_min[imp])
        k = max(len(a) for a in ts)
        self.imp_t = np.full((BASELINE_RUNS, k), np.inf)
        self.imp_b = np.full((BASELINE_RUNS, k), worst)
        for i, (a, b) in enumerate(zip(ts, bs)):
            self.imp_t[i, :len(a)] = a
            self.imp_b[i, :len(b)] = b

    def baseline(self, t: np.ndarray) -> np.ndarray:
        """Mean best-so-far of the virtual runs at times ``t``; a run with
        nothing found yet counts the worst finite value."""
        counts = (self.imp_t[:, :, None] <= t[None, None, :]).sum(axis=1)
        vals = np.take_along_axis(self.imp_b, np.maximum(counts - 1, 0),
                                  axis=1)
        return np.where(counts > 0, vals, self.values[-1]).mean(axis=0)

    def score_trace(self, trace: list) -> np.ndarray:
        best = INF
        ts, bs = [], []
        for t_cum, value in trace:
            if value < best:
                best = value
                ts.append(t_cum)
                bs.append(best)
        out = np.zeros(N_SAMPLES)
        for j, t in enumerate(self.times):
            k = np.searchsorted(ts, t, side="right") - 1
            if k < 0 or not math.isfinite(bs[k]):
                continue
            denom = self.base_at[j] - self.optimum
            if denom <= 0:
                out[j] = 1.0 if bs[k] <= self.optimum else 0.0
            else:
                out[j] = (self.base_at[j] - bs[k]) / denom
        return out


def repeat_rng(name: str, repeat: int, seed: int) -> random.Random:
    return random.Random((seed * 1_000_003 + repeat)
                         ^ zlib.crc32(name.encode()))


def score(scorers: list, strategy: str, hyperparams: dict, repeats: int,
          seed: int, accum: str = "float64") -> dict:
    """``{"score": aggregate, "per_space": {name: score},
    "simulated_seconds": budget spent over all runs, "fresh_evals": ...}``
    of one strategy configuration (Eq. 3)."""
    fn = STRATEGIES[strategy]
    curves = {}
    simulated, fresh = 0.0, 0
    for sc in scorers:
        acc = np.zeros(N_SAMPLES)
        for r in range(repeats):
            run = Run(sc.space, sc.budget, accum)
            try:
                fn(run, repeat_rng(sc.space.name, r, seed), **hyperparams)
            except Exhausted:
                pass
            acc += sc.score_trace(run.trace)
            simulated += float(run.spent)
            fresh += len(run.trace)
        curves[sc.space.name] = acc / repeats
    mean_curve = np.mean(np.stack(list(curves.values())), axis=0)
    return {"score": float(mean_curve.mean()),
            "per_space": {k: float(c.mean()) for k, c in curves.items()},
            "simulated_seconds": simulated, "fresh_evals": fresh}
