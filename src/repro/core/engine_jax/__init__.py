"""Jitted JAX replay engine (ROADMAP item 2: the simulator on the accelerator).

Two execution modes with two different parity contracts:

  * **Replay-from-log** (``ReplayEngine`` behind
    ``SimulationRunner(engine="jax")``, ``replay_many`` for fused multi-run
    workloads): the compiled space/cache tables live as device arrays and a
    ``lax.scan`` performs the budget accounting with the exact left-to-right
    float64 additions of the numpy engine. Given identical told
    observations, scores and traces are **bit-identical** to the numpy
    path — the numpy engine stays the parity oracle exactly as
    ``core.space.reference`` anchors compiled spaces
    (tests/test_engine_jax.py).

  * **Free-running** (``free_run``): GA / PSO / DE / random search step as
    pure-functional state transitions under ``jax.vmap`` over runs, with
    ``lax.scan`` driving whole generations so thousands of concurrent runs
    resolve in one dispatch. Device-side RNG (threefry) cannot replay
    numpy's ``Generator``/``random.Random`` streams, so this mode is
    **statistically equivalent** only: pinned seeds reproduce bit-for-bit
    against themselves, and distributions match the numpy strategies
    (docs/performance.md explains the contract).

``engine="jax"`` always dispatches through JAX, on whatever platform JAX
initialized; it never degrades to numpy. Float64 is enabled per dispatch
via ``jax.enable_x64``, so the engine does not depend on (or mutate) the
process-global ``JAX_ENABLE_X64`` setting.
"""
from __future__ import annotations

from .campaign import FUSED_STRATEGIES, FusedRun  # noqa: F401
from .campaign import drive_fused, fuse_reason  # noqa: F401
from .replay import ReplayEngine, replay_many  # noqa: F401
from .strategies import FREE_RUN_STRATEGIES, free_run  # noqa: F401
from .tables import ReplayTables, SpaceTables  # noqa: F401
from .tables import replay_tables, space_tables  # noqa: F401
