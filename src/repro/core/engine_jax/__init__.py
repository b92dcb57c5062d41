"""Jitted JAX replay engine (ROADMAP item 2: the simulator on the accelerator).

One contract: **replay-from-log, bit-identical.** The compiled space/cache
tables live as device arrays and a ``lax.scan`` performs the budget
accounting with the exact left-to-right float64 additions of the numpy
engine. Given identical told observations, scores and traces are
bit-identical to the numpy path — the numpy engine stays the parity
oracle exactly as ``core.space.reference`` anchors compiled spaces. It
runs two ways:

  * **per ask** (``ReplayEngine`` behind ``SimulationRunner(engine="jax")``,
    ``replay_many`` for R runs' row segments at once): one dispatch
    resolves one batch of asked rows (tests/test_engine_jax.py);
  * **per campaign** (``drive_fused``): the real strategy code steps on the
    host as a trajectory oracle while one vmapped dispatch per segment does
    the budget accounting of every concurrent run
    (tests/test_campaign_fused.py).

``engine="jax"`` always dispatches through JAX, on whatever platform JAX
initialized; it never degrades to numpy. Float64 is enabled per dispatch
via ``jax.enable_x64``, so the engine does not depend on (or mutate) the
process-global ``JAX_ENABLE_X64`` setting.
"""
from __future__ import annotations

from .campaign import FUSED_STRATEGIES, FusedRun  # noqa: F401
from .campaign import drive_fused, fuse_reason  # noqa: F401
from .replay import ReplayEngine, replay_many  # noqa: F401
from .tables import ReplayTables, replay_tables  # noqa: F401
