"""Free-running population strategies on the device (vmap over runs).

Ports of the numpy GA / PSO / DE / random-search to pure-functional state
transitions: each strategy is a namespace of ``init``/``ask``/``tell``
functions over an explicit pytree state, stepped by one ``lax.scan`` over
generations inside ``free_run`` and vmapped over runs — R concurrent runs
x G generations resolve in a single dispatch.

Parity contract (see docs/performance.md): this mode is *statistically*
equivalent to the numpy strategies, not bit-identical. Device RNG is
threefry — it cannot replay ``random.Random``/``np.random.Generator``
streams — and two algorithmic substitutions keep the transitions
device-friendly:

  * repair: an invalid child/decode restarts at a uniform random valid row
    instead of walking the BFS nearest-valid move tables (the tables are
    host-side ragged structures);
  * GA ``disruptive_uniform`` crossover falls back to ``uniform`` (the
    guaranteed-half-swap needs data-dependent shuffling of the differing
    gene set).

Everything on the budget side *is* exact: generations charge through the
same ``budget_scan`` as replay-from-log (left-to-right float64 on bit
patterns, fresh-only, pre-eval exhaustion check), revisits are free via a
per-run ``seen`` bitmap, and a run freezes at the generation where the
numpy driver would have caught ``BudgetExhausted``. Pinned seeds
reproduce bit-for-bit against themselves on a given backend. The best
value is tracked as an int64 key of its bit pattern (``_order_key``), so
``best_value`` and ``curve_best`` are recorded values exactly; only the
fitness the strategies steer by is native float64, which a TPU holds as a
pair of float32.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64

from ..strategies.base import FAILURE_FITNESS
from .replay import _F64_INF, _NO_MAX_E, _NO_MAX_S, budget_scan
from .tables import as_f64, f64_bits, replay_tables, space_tables


_LOW63 = 0x7FFF_FFFF_FFFF_FFFF
_EXP = 0x7FF << 52               # exponent bits: all set for inf and NaN


def _order_key(bits):
    """float64 bit patterns (int64) -> int64 keys that order as the values
    do (NaN aside). Negative values have their magnitude bits flipped; the
    map is its own inverse."""
    return jnp.where(bits < 0, bits ^ _LOW63, bits)


def _rand_rows(key, n_valid: int, shape) -> jnp.ndarray:
    return jax.random.randint(key, shape, 0, n_valid)


def _flat(k, strides):
    """Flat Cartesian index of value-index rows ``k`` (int64). A sum of
    products rather than ``k @ strides``: a TPU has no int64 dot."""
    return jnp.sum(k.astype(jnp.int64) * strides, axis=-1, dtype=jnp.int64)


def _decode(x, st, key):
    """Round/clip a (P, T) continuous index matrix to rows; invalid
    positions restart at a uniform random valid row (device-side stand-in
    for the BFS repair tables)."""
    k = jnp.clip(jnp.rint(x), 0.0, st["x_hi"]).astype(jnp.int64)
    flat = _flat(k, st["strides"])
    rows = st["row_of_flat"][flat].astype(jnp.int32)
    rnd = _rand_rows(key, st["n_valid"], rows.shape).astype(jnp.int32)
    return jnp.where(rows < 0, rnd, rows)


# --------------------------------------------------------------- crossovers
def _cross_uniform(a, b, key, T):
    mask = jax.random.bernoulli(key, 0.5, a.shape)
    return jnp.where(mask, b, a), jnp.where(mask, a, b)


def _cross_single_point(a, b, key, T):
    if T < 2:
        return a, b
    pt = jax.random.randint(key, (a.shape[0],), 1, T)
    mask = jnp.arange(T)[None, :] >= pt[:, None]
    return jnp.where(mask, b, a), jnp.where(mask, a, b)


def _cross_two_point(a, b, key, T):
    if T < 3:
        return _cross_single_point(a, b, key, T)
    ki, kj = jax.random.split(key)
    i = jax.random.randint(ki, (a.shape[0],), 1, T)
    j = jax.random.randint(kj, (a.shape[0],), 1, T - 1)
    j = j + (j >= i)  # distinct uniform pair from 1..T-1
    lo, hi = jnp.minimum(i, j), jnp.maximum(i, j)
    ar = jnp.arange(T)[None, :]
    mask = (ar >= lo[:, None]) & (ar < hi[:, None])
    return jnp.where(mask, b, a), jnp.where(mask, a, b)


_CROSSOVERS = {
    "single_point": _cross_single_point,
    "two_point": _cross_two_point,
    "uniform": _cross_uniform,
    # device fallback: the disruptive variant's guaranteed-half swap of the
    # differing-gene set is data-dependent; plain uniform is the closest
    # shape-static operator
    "disruptive_uniform": _cross_uniform,
}


# ---------------------------------------------------------------- strategies
class _GA:
    name = "genetic_algorithm"
    defaults = {"method": "uniform", "popsize": 20, "maxiter": 100,
                "mutation_chance": 10}

    @staticmethod
    def init(st, P, hp):
        return {"pop": jnp.zeros((P, st["n_tunables"]), jnp.int32),
                "it": jnp.int32(0)}

    @staticmethod
    def ask(state, key, st, P, hp):
        need = state["it"] == 0
        init_pop = st["vidx"][_rand_rows(key, st["n_valid"], (P,))]
        pop = jnp.where(need, init_pop, state["pop"])
        rows = st["row_of_flat"][_flat(pop, st["strides"])]
        return rows.astype(jnp.int32), {**state, "pop": pop}

    @staticmethod
    def tell(state, rows, fitness, key, st, P, hp):
        T = st["n_tunables"]
        crossover = _CROSSOVERS[str(hp["method"])]
        p_mut = 1.0 / float(hp["mutation_chance"])
        pop = state["pop"]
        ranked = pop[jnp.argsort(fitness)]  # stable: ties by index
        n_pairs = max(1, (P - 1 + 1) // 2)
        kp, kc, km, kg, kr = jax.random.split(key, 5)
        # rank-weighted parent selection: best gets weight P, worst 1
        logits = jnp.log(jnp.arange(P, 0, -1).astype(jnp.float64))
        parents = jax.random.categorical(kp, logits, shape=(n_pairs, 2))
        c1, c2 = crossover(ranked[parents[:, 0]], ranked[parents[:, 1]],
                           kc, T)
        children = jnp.stack([c1, c2], axis=1).reshape(2 * n_pairs, T)[:P - 1]
        # per-gene mutation to a uniform value index of that tunable
        mut = jax.random.uniform(km, children.shape) < p_mut
        cards = jnp.asarray(st["cards"], dtype=jnp.float64)
        draws = jnp.floor(jax.random.uniform(kg, children.shape)
                          * cards[None, :]).astype(jnp.int32)
        children = jnp.where(mut, draws, children)
        # repair: invalid offspring restart at a random valid genome
        flat = _flat(children, st["strides"])
        bad = st["row_of_flat"][flat] < 0
        rescue = st["vidx"][_rand_rows(kr, st["n_valid"], (P - 1,))]
        children = jnp.where(bad[:, None], rescue, children)
        new_pop = jnp.concatenate([ranked[:1], children], axis=0)  # elitism
        it = state["it"] + 1
        it = jnp.where(it >= int(hp["maxiter"]), 0, it)  # restart
        return {"pop": new_pop, "it": it}


class _PSO:
    name = "pso"
    defaults = {"popsize": 20, "maxiter": 100, "c1": 2.0, "c2": 1.0,
                "w": 0.5}

    @staticmethod
    def init(st, P, hp):
        T = st["n_tunables"]
        return {"pos": jnp.zeros((P, T)), "vel": jnp.zeros((P, T)),
                "pbest": jnp.zeros((P, T)), "pbest_f": jnp.full(P, jnp.inf),
                "gbest": jnp.zeros(T), "gbest_f": jnp.inf,
                "it": jnp.int32(0)}

    @staticmethod
    def ask(state, key, st, P, hp):
        need = state["it"] == 0
        k1, k2, k3 = jax.random.split(key, 3)
        span = jnp.maximum(st["x_hi"], 1.0)
        pos0 = st["vidx"][_rand_rows(k1, st["n_valid"], (P,))].astype(
            jnp.float64)
        vel0 = jax.random.uniform(k2, pos0.shape, minval=-1.0,
                                  maxval=1.0) * span * 0.25
        pos = jnp.where(need, pos0, state["pos"])
        state = {**state,
                 "pos": pos,
                 "vel": jnp.where(need, vel0, state["vel"]),
                 "pbest": jnp.where(need, pos, state["pbest"]),
                 "pbest_f": jnp.where(need, jnp.inf, state["pbest_f"]),
                 "gbest": jnp.where(need, pos[0], state["gbest"]),
                 "gbest_f": jnp.where(need, jnp.inf, state["gbest_f"])}
        return _decode(pos, st, k3), state

    @staticmethod
    def tell(state, rows, fitness, key, st, P, hp):
        c1, c2 = float(hp["c1"]), float(hp["c2"])
        w = float(hp["w"])
        span = jnp.maximum(st["x_hi"], 1.0)
        x = st["vidx"][rows].astype(jnp.float64)
        better = fitness < state["pbest_f"]
        pbest = jnp.where(better[:, None], x, state["pbest"])
        pbest_f = jnp.where(better, fitness, state["pbest_f"])
        # sequential global-best update == first index achieving the min
        i = jnp.argmin(fitness)
        gb = fitness[i] < state["gbest_f"]
        gbest = jnp.where(gb, x[i], state["gbest"])
        gbest_f = jnp.where(gb, fitness[i], state["gbest_f"])
        k1, k2 = jax.random.split(key)
        pos = state["pos"]
        r1 = jax.random.uniform(k1, pos.shape)
        r2 = jax.random.uniform(k2, pos.shape)
        vel = (w * state["vel"] + c1 * r1 * (pbest - pos)
               + c2 * r2 * (gbest - pos))
        vel = jnp.clip(vel, -span, span)
        pos = jnp.clip(pos + vel, 0.0, st["x_hi"])
        it = state["it"] + 1
        it = jnp.where(it >= int(hp["maxiter"]), 0, it)
        return {"pos": pos, "vel": vel, "pbest": pbest, "pbest_f": pbest_f,
                "gbest": gbest, "gbest_f": gbest_f, "it": it}


class _DE:
    """DE/rand/1/bin, deferred updating (the whole-generation batch form —
    immediate updating is inherently sequential per member)."""

    name = "differential_evolution"
    defaults = {"popsize": 20, "maxiter": 100, "F": 0.8, "CR": 0.9}

    @staticmethod
    def init(st, P, hp):
        T = st["n_tunables"]
        return {"pop": jnp.zeros((P, T)), "fit": jnp.full(P, jnp.inf),
                "trial": jnp.zeros((P, T)), "initgen": jnp.bool_(True),
                "it": jnp.int32(0)}

    @staticmethod
    def ask(state, key, st, P, hp):
        F, CR = float(hp["F"]), float(hp["CR"])
        T = st["n_tunables"]
        need = state["it"] == 0
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        pop0 = st["vidx"][_rand_rows(k1, st["n_valid"], (P,))].astype(
            jnp.float64)
        pop = jnp.where(need, pop0, state["pop"])
        # a,b,c: distinct members != i, via argsort of uniforms with the
        # diagonal masked (uniform ordered sample without replacement)
        u = jax.random.uniform(k2, (P, P)) + 2.0 * jnp.eye(P)
        abc = jnp.argsort(u, axis=1)[:, :3]
        a, b, c = pop[abc[:, 0]], pop[abc[:, 1]], pop[abc[:, 2]]
        mutant = jnp.clip(a + F * (b - c), 0.0, st["x_hi"])
        cross = jax.random.uniform(k3, (P, T)) < CR
        forced = jax.random.randint(k4, (P,), 0, T)
        cross = cross | (jnp.arange(T)[None, :] == forced[:, None])
        trial = jnp.where(cross, mutant, pop)
        trial = jnp.where(need, pop, trial)  # init generation asks the pop
        state = {**state, "pop": pop, "trial": trial, "initgen": need}
        return _decode(trial, st, k5), state

    @staticmethod
    def tell(state, rows, fitness, key, st, P, hp):
        initgen = state["initgen"]
        sel = initgen | (fitness <= state["fit"])
        pop = jnp.where(sel[:, None], state["trial"], state["pop"])
        fit = jnp.where(sel, fitness, state["fit"])
        it = state["it"] + 1
        it = jnp.where(it >= int(hp["maxiter"]) + 1, 0, it)
        return {**state, "pop": pop, "fit": fit, "it": it,
                "initgen": jnp.bool_(False)}


class _RandomSearch:
    """Sampling without replacement: one device permutation per run,
    consumed ``popsize`` rows per generation (the numpy strategy asks the
    whole permutation at once; chunking it per generation is observably
    identical under free budgets because revisits never occur)."""

    name = "random_search"
    defaults = {"popsize": 20}

    @staticmethod
    def init(st, P, hp):
        return {"perm": jnp.zeros(st["n_valid"], jnp.int32),
                "offset": jnp.int32(0), "it": jnp.int32(0)}

    @staticmethod
    def ask(state, key, st, P, hp):
        need = state["it"] == 0
        perm0 = jax.random.permutation(key, st["n_valid"]).astype(jnp.int32)
        perm = jnp.where(need, perm0, state["perm"])
        offset = jnp.where(need, 0, state["offset"])
        rows = jax.lax.dynamic_slice(perm, (offset,), (P,))
        return rows, {**state, "perm": perm, "offset": offset}

    @staticmethod
    def tell(state, rows, fitness, key, st, P, hp):
        # past the end, dynamic_slice clamps: the tail re-asks seen rows,
        # which are free revisits — same no-op as the finished numpy ask
        offset = jnp.minimum(state["offset"] + P,
                             max(0, st["n_valid"] - P))
        return {**state, "offset": offset, "it": state["it"] + 1}


FREE_RUN_STRATEGIES = {s.name: s for s in (_GA, _PSO, _DE, _RandomSearch)}


# ------------------------------------------------------------------ driver
@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _free_run_jit(impl, P, G, hp_key, cards, keys, col_of_row, time_s,
                  charge_s, vidx, row_of_flat, strides, x_hi, mean_charge,
                  max_s, max_e):
    hp = dict(hp_key)
    n_valid, T = vidx.shape
    st = {"vidx": vidx, "row_of_flat": row_of_flat, "strides": strides,
          "x_hi": x_hi, "n_valid": int(n_valid), "n_tunables": int(T),
          "cards": cards}

    def one_run(key):
        k_loop = key
        state0 = impl.init(st, P, hp)
        carry0 = (state0, k_loop, jnp.zeros(n_valid, bool),
                  jnp.int64(0), jnp.int64(0),  # spend: bits of 0.0
                  jnp.int64(_F64_INF), jnp.int32(-1), jnp.int64(0),
                  jnp.bool_(False))

        def gen(carry, _):
            (state, key, seen, spent, evals, best_v, best_r, fresh_n,
             stopped) = carry
            key2, k_ask, k_tell = jax.random.split(key, 3)
            rows, state_a = impl.ask(state, k_ask, st, P, hp)
            # within-generation first occurrence: P is population-sized,
            # so the P x P pairwise compare beats any n_valid-sized scatter
            i = jnp.arange(P)
            dup = (rows[:, None] == rows[None, :]) & (i[:, None] > i[None, :])
            fresh = ~jnp.any(dup, axis=1) & ~seen[rows]
            col = col_of_row[rows]
            miss = col < 0
            safe = jnp.clip(col, 0)
            bits = jnp.where(miss, _F64_INF, time_s[safe])
            finite = (bits & _EXP) != _EXP
            charge = jnp.where(miss, mean_charge, charge_s[safe])
            accept, _t, spent2, evals2, exh = budget_scan(
                fresh, charge, spent, evals, max_s, max_e)
            seen2 = seen.at[rows].max(accept)
            fresh_n2 = fresh_n + jnp.sum(accept)
            okv = jnp.where(accept & finite, _order_key(bits), _F64_INF)
            j = jnp.argmin(okv)
            better = okv[j] < best_v
            best_v2 = jnp.where(better, okv[j], best_v)
            best_r2 = jnp.where(better, rows[j], best_r).astype(jnp.int32)
            value = jax.lax.bitcast_convert_type(bits, jnp.float64)
            fitness = jnp.where(finite, value, FAILURE_FITNESS)
            state_b = impl.tell(state_a, rows, fitness, k_tell, st, P, hp)
            # once exhausted the numpy driver stops stepping the strategy;
            # budget/seen/best are already monotone-frozen (no accepts can
            # follow a rejection), so only state + rng need the freeze
            state_c = jax.tree_util.tree_map(
                lambda old, new: jnp.where(stopped, old, new), state, state_b)
            key3 = jnp.where(stopped, key, key2)
            carry2 = (state_c, key3, seen2, spent2, evals2, best_v2,
                      best_r2, fresh_n2, stopped | exh)
            return carry2, (spent2, best_v2)

        carry, (curve_spent, curve_best) = jax.lax.scan(
            gen, carry0, None, length=G)
        (_state, _key, _seen, spent, evals, best_v, best_r, fresh_n,
         stopped) = carry
        return {"best_value": best_v, "best_row": best_r,
                "spent_seconds": spent, "spent_evals": evals,
                "fresh_evals": fresh_n, "exhausted": stopped,
                "curve_spent": curve_spent, "curve_best": curve_best}

    return jax.vmap(one_run)(keys)


def free_run(cache, strategy: str = "genetic_algorithm", *, runs: int = 32,
             seed: int = 0, generations: "int | None" = None,
             max_seconds: "float | None" = None,
             max_evals: "int | None" = None, **hyperparams) -> dict:
    """Run ``runs`` independent free-running campaigns of ``strategy`` on
    the device in one dispatch; returns numpy arrays keyed like
    ``SearchDriver`` observables (best value/row, spend, fresh evals,
    per-generation spend/best curves of shape (runs, generations)).

    Pinned-seed deterministic; statistically equivalent to the numpy
    strategies (module docstring has the exact contract)."""
    impl = FREE_RUN_STRATEGIES[strategy]
    unknown = set(hyperparams) - set(impl.defaults)
    if unknown:
        raise ValueError(f"{strategy}: unknown hyperparameters "
                         f"{sorted(unknown)}")
    hp = {**impl.defaults, **hyperparams}
    compiled = cache.space.compiled
    cols = cache.columns
    rt = replay_tables(cols, compiled)
    st = space_tables(compiled)
    if not compiled.n_valid:
        raise ValueError(f"space {compiled.name!r} has no valid configs")
    P = int(hp.get("popsize", 20))
    G = int(generations if generations is not None
            else hp.get("maxiter", 100))
    mean_charge = cache.mean_eval_charge() if rt.has_miss else 0.0
    max_s = _NO_MAX_S if max_seconds is None else float(max_seconds)
    max_e = _NO_MAX_E if max_evals is None else int(max_evals)
    hp_key = tuple(sorted(hp.items()))
    with enable_x64():
        keys = jax.random.split(jax.random.PRNGKey(int(seed)), int(runs))
        out = _free_run_jit(impl, P, G, hp_key, st.cards, keys,
                            rt.col_of_row, rt.time_s, rt.charge_s,
                            st.vidx, st.row_of_flat, st.strides, st.x_hi,
                            jnp.asarray(f64_bits(mean_charge)),
                            jnp.asarray(f64_bits(max_s)), jnp.int64(max_e))
        out = {k: np.asarray(v) for k, v in out.items()}
    for k in ("spent_seconds", "curve_spent"):
        out[k] = as_f64(out[k])
    for k in ("best_value", "curve_best"):  # keys -> bits -> values
        out[k] = as_f64(np.where(out[k] < 0, out[k] ^ _LOW63, out[k]))
    return out
