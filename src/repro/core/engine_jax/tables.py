"""Device-resident mirrors of the compiled space and cache columns.

The numpy arrays stay the source of truth; these are one-time ``device_put``
copies memoized on their host objects (``CacheColumns._jax``,
``CompiledSpace._jax``) with the same single-entry protocol as
``CacheColumns.rows_for_space``. They are never pickled: both hosts drop
the memo in ``__getstate__``/``__reduce__`` paths, so a process-pool worker
rebuilds its tables against whatever backend it actually has
(tests/test_parallel.py pins this).

The cache's float64 charge/time columns are held as their int64 bit
patterns (``f64_bits``), and every table is created under
``jax.enable_x64`` — jax's default 32-bit types would silently truncate
them and break the bit-parity contract (the ``JAX_ENABLE_X64`` CI row
guards the other direction: the suite must also pass when x64 is on
globally).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import enable_x64


def f64_bits(x) -> np.ndarray:
    """Host float64 value(s) -> their IEEE-754 bit patterns as int64."""
    return np.asarray(x, dtype=np.float64).view(np.int64)


def as_f64(bits) -> np.ndarray:
    """Device or host int64 bit patterns -> host float64 values."""
    return np.asarray(bits).view(np.float64)


class ReplayTables:
    """Replay-from-log tables for one (CacheColumns, CompiledSpace) pair:
    the space-row -> cache-row bridge plus the value/charge columns (as
    float64 bit patterns).

    A replay dispatch against these tables moves its per-call data with
    ``device_put`` (one transfer call for all its inputs) and
    ``device_get`` (one for all the outputs the host reads); ``transfers``
    counts those calls, two a dispatch."""

    __slots__ = ("n_valid", "col_of_row", "time_s", "charge_s", "has_miss",
                 "transfers")

    def __init__(self, cols, compiled):
        col_map = cols.rows_for_space(compiled)
        with enable_x64():
            self.col_of_row = jnp.asarray(col_map, dtype=jnp.int32)
            self.time_s = jnp.asarray(f64_bits(cols.time_s))
            self.charge_s = jnp.asarray(f64_bits(cols.charge_s))
        self.n_valid = int(compiled.n_valid)
        self.has_miss = bool((col_map < 0).any()) if len(col_map) else False
        self.transfers = 0

    def device_put(self, inputs: tuple) -> tuple:
        """A dispatch's host inputs on the device, in one transfer call.
        Under ``enable_x64``, so int64 bit patterns stay int64."""
        self.transfers += 1
        with enable_x64():
            return jax.device_put(inputs)

    def device_get(self, outputs: tuple) -> tuple:
        """A dispatch's outputs on the host, in one transfer call: every
        copy starts before any is waited on, so their latencies overlap
        instead of adding up."""
        self.transfers += 1
        return jax.device_get(outputs)


class SpaceTables:
    """Free-running tables for one ``CompiledSpace``: the value-index
    matrix, validity lookup, and strides (device-side decode/repair)."""

    __slots__ = ("n_valid", "n_tunables", "cards", "vidx", "row_of_flat",
                 "strides", "x_hi")

    def __init__(self, compiled):
        with enable_x64():
            self.vidx = jnp.asarray(compiled.vidx, dtype=jnp.int32)
            self.row_of_flat = jnp.asarray(compiled.row_of_flat)
            self.strides = jnp.asarray(compiled.strides_np)
            self.x_hi = jnp.asarray(compiled._x_hi)
        self.n_valid = int(compiled.n_valid)
        self.n_tunables = int(compiled.n_tunables)
        self.cards = tuple(compiled.cards)


def replay_tables(cols, compiled) -> ReplayTables:
    """Memoized ``ReplayTables`` (single-entry, keyed by compiled-space
    identity — like ``CacheColumns.rows_for_space``)."""
    memo = cols._jax
    if memo is not None and memo[0] is compiled:
        return memo[1]
    tables = ReplayTables(cols, compiled)
    cols._jax = (compiled, tables)
    return tables


def space_tables(compiled) -> SpaceTables:
    """Memoized ``SpaceTables`` on the compiled space itself."""
    tables = compiled._jax
    if tables is None:
        tables = compiled._jax = SpaceTables(compiled)
    return tables
