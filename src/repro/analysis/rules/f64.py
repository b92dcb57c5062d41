"""float64 budget-discipline rules for the jitted engine.

Replay is bit-exact because budget spend accumulates left-to-right in
float64 (core/engine_jax/replay.py's ``budget_scan``; the module
docstring is explicit that any parallel scan reassociates the additions
and drifts by ULPs). Statically enforceable corollaries for everything
under ``core/engine_jax/``:

  * no ``jnp.cumsum``/``cumprod``/``associative_scan`` — parallel scans
    reassociate; sequential accumulation must go through ``lax.scan``;
  * no float32 literals/dtypes — the tables are float64 mirrors of the
    cache columns, and a float32 intermediate silently truncates them;
  * reductions spell out their dtype — without one, ``jnp.sum``'s
    accumulator dtype depends on the ambient ``enable_x64`` context.
"""
from __future__ import annotations

import ast

from ..core import ERROR, WARNING, Rule, call_name, dotted

_JNP_ROOTS = ("jnp", "jax.numpy")


def _jnp_call(node: ast.Call, names: tuple) -> str | None:
    full = call_name(node)
    if full is None:
        return None
    for root in _JNP_ROOTS:
        for fn in names:
            if full == f"{root}.{fn}":
                return fn
    return None


def _has_kwarg(node: ast.Call, name: str) -> bool:
    return any(kw.arg == name for kw in node.keywords)


class ParallelScanOnDevice(Rule):
    name = "f64-parallel-scan"
    severity = ERROR
    scope = ("core/engine_jax/",)
    invariant = ("budget/spend accumulation is left-to-right float64 via "
                 "lax.scan; parallel prefix scans reassociate and drift")
    oracle = ("scalar-vs-jax commit parity incl. exhaustion points "
              "(tests/test_engine_jax.py)")

    def visit_Call(self, ctx, node):
        fn = _jnp_call(node, ("cumsum", "cumprod", "nancumsum"))
        full = call_name(node)
        if fn is None and full in ("lax.associative_scan",
                                   "jax.lax.associative_scan"):
            fn = "associative_scan"
        if fn is not None:
            yield self.finding(
                ctx, node,
                f"{full}() is a parallel scan — it reassociates float "
                f"additions and breaks bit-parity with the sequential "
                f"numpy accumulation; use lax.scan (see budget_scan)")


class ReductionWithoutDtype(Rule):
    name = "f64-sum-dtype"
    severity = WARNING
    scope = ("core/engine_jax/",)
    invariant = ("device reductions pin their accumulator dtype; the "
                 "default depends on the ambient enable_x64 context")
    oracle = ("JAX_ENABLE_X64=1 CI row — the suite must pass with x64 on "
              "globally and off")

    def visit_Call(self, ctx, node):
        fn = _jnp_call(node, ("sum", "prod", "nansum", "nanprod", "trace"))
        if fn is not None and not _has_kwarg(node, "dtype"):
            yield self.finding(
                ctx, node,
                f"jnp.{fn}() without an explicit dtype= — the accumulator "
                f"dtype flips with the enable_x64 context; pin it "
                f"(dtype=jnp.float64 for budget/spend, jnp.int* for "
                f"counters)")


class Float32Literal(Rule):
    name = "f64-float32-literal"
    severity = ERROR
    scope = ("core/engine_jax/",)
    invariant = ("the replay tables and commit path are float64 "
                 "end-to-end; a float32 cast silently truncates the "
                 "cache's charge/time columns")
    oracle = ("float64 columns mirrored as int64 bit patterns under "
              "enable_x64 (core/engine_jax/tables.py) + replay "
              "bit-parity tests")

    def visit_Attribute(self, ctx, node):
        if node.attr != "float32":
            return
        name = dotted(node)
        if name in ("jnp.float32", "np.float32", "numpy.float32",
                    "jax.numpy.float32"):
            yield self.finding(
                ctx, node,
                f"{name} in the jitted engine — replay tables are "
                f"float64 by contract; a float32 cast truncates "
                f"charge/time columns and breaks bit-parity")

    def visit_Call(self, ctx, node):
        # dtype="float32" string form
        for kw in node.keywords:
            if kw.arg == "dtype" and isinstance(kw.value, ast.Constant) \
                    and kw.value.value == "float32":
                yield self.finding(
                    ctx, node,
                    'dtype="float32" in the jitted engine — replay '
                    'tables are float64 by contract')
