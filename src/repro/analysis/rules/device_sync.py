"""Device→host sync discipline for the jitted engine's hot loops.

The fused-campaign throughput budget (docs/performance.md, "host↔device
round-trip budget") hinges on one shape: a handful of vmapped dispatches,
then *one* batched fetch of the outputs (``device_get``). An implicit
element-wise sync —
``np.asarray``/``float()``/``.item()``/``.tolist()`` applied to a jax
array inside a loop body — blocks on the device once per iteration and
silently turns an O(dispatches) campaign back into the O(evaluations)
round-trip pattern the fused executor exists to remove.

The rule is a conservative local dataflow with one structural judgment,
"convert where you dispatch": names assigned from ``jnp.*``/``jax.*``
calls or jitted callables (any callable whose name contains ``jit``) are
device values, and converting one inside a loop is an error **unless** the
value was produced inside the same innermost loop's per-iteration region —
the batched-output idiom of ``campaign._drive_group`` (dispatch in the
loop, one batched fetch right after it) stays clean,
while per-element syncs of device values produced outside the loop (the
``(np.asarray(o) for o in out)`` shape) are flagged. A conversion's *result* is a host value: ``spent =
np.asarray(out[4])`` then ``float(spent[i])`` in a loop syncs nothing.
``jax.device_get`` and any method named ``device_get`` (the replay
tables' batched fetch) convert like ``np.asarray``: their results are
host arrays.
"""
from __future__ import annotations

import ast

from ..core import ERROR, Rule, call_name

# conversion callables that force a device→host transfer per call
_CONVERT_CALLS = frozenset({
    "np.asarray", "numpy.asarray", "np.array", "numpy.array", "float",
})
# conversion methods on array receivers
_CONVERT_METHODS = frozenset({"item", "tolist"})
# batched device->host fetches: ``jax.device_get`` or a method of that name
_FETCH = "device_get"

_DEVICE_ROOTS = ("jnp", "jax")

_LOOPS = (ast.For, ast.While, ast.GeneratorExp, ast.ListComp,
          ast.SetComp, ast.DictComp)


def _is_convert_call(name: "str | None") -> bool:
    """A call that converts its first argument to host arrays."""
    return name is not None and (name in _CONVERT_CALLS
                                 or name.rsplit(".", 1)[-1] == _FETCH)


def _is_device_call(node: ast.Call) -> bool:
    name = call_name(node)
    if name is None:
        return False
    root = name.split(".", 1)[0]
    if root in _DEVICE_ROOTS:
        return True
    return "jit" in name.rsplit(".", 1)[-1]


def _is_conversion(node: ast.AST) -> bool:
    """Top-level host conversion: its result lives on the host."""
    if not isinstance(node, ast.Call):
        return False
    if _is_convert_call(call_name(node)):
        return True
    if not isinstance(node.func, ast.Attribute):
        return False
    # a method of a converted array (``np.asarray(x).view(...)``) stays
    # on the host
    return (node.func.attr in _CONVERT_METHODS
            or _is_conversion(node.func.value))


def _target_names(target: ast.AST):
    if isinstance(target, ast.Name):
        yield target.id
    elif isinstance(target, (ast.Tuple, ast.List)):
        for elt in target.elts:
            yield from _target_names(elt)


def _device_names_in(expr: ast.AST, device: set) -> set:
    return {n.id for n in ast.walk(expr)
            if isinstance(n, ast.Name) and n.id in device}


def _refs_device(expr: ast.AST, device: set) -> bool:
    for node in ast.walk(expr):
        if isinstance(node, ast.Name) and node.id in device:
            return True
        if isinstance(node, ast.Call) and _is_device_call(node):
            return True
    return False


def _walk_function(func: ast.AST):
    """Every node of ``func``'s own body, skipping nested function defs
    (they get their own visit)."""
    stack = list(ast.iter_child_nodes(func))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _device_assigns(node: ast.AST, device: set):
    """(targets-iterable, value) pairs for assignments whose value is a
    device expression (and not a top-level host conversion)."""
    if isinstance(node, ast.Assign):
        value, targets = node.value, node.targets
    elif isinstance(node, (ast.AnnAssign, ast.AugAssign)):
        value, targets = node.value, [node.target]
    else:
        return
    if value is None or _is_conversion(value) \
            or not _refs_device(value, device):
        return
    for t in targets:
        yield from _target_names(t)


def _collect_device_names(func: ast.AST) -> set:
    """Fixpoint over assignments/loop targets: names holding device
    values. Conversion results are host values and do not propagate."""
    device: set = set()
    for _ in range(3):
        before = len(device)
        for node in _walk_function(func):
            device.update(_device_assigns(node, device))
            if isinstance(node, ast.For) \
                    and _refs_device(node.iter, device):
                device.update(_target_names(node.target))
            elif isinstance(node, ast.comprehension) \
                    and _refs_device(node.iter, device):
                device.update(_target_names(node.target))
        if len(device) == before:
            break
    return device


def _loop_region_defs(loop: ast.AST, device: set) -> set:
    """Device names produced inside ``loop``'s per-iteration region —
    converting these where they were dispatched is the blessed idiom."""
    defs: set = set()
    if isinstance(loop, (ast.For, ast.While)):
        region = list(loop.body) + list(loop.orelse)
        if isinstance(loop, ast.While):
            region.append(loop.test)
        for stmt in region:
            for node in ast.walk(stmt):
                defs.update(_device_assigns(node, device))
    # comprehensions assign nothing: defs stay empty, every outside
    # device name converted per-element is a violation
    return defs


class DeviceSyncInLoop(Rule):
    name = "device-sync-in-loop"
    severity = ERROR
    scope = ("core/engine_jax/",)
    invariant = ("engine_jax hot loops never convert device arrays "
                 "element-wise: np.asarray/float()/.item()/.tolist() on "
                 "a device value inside a loop body is an error unless "
                 "the value was dispatched in that same loop iteration")
    oracle = ("fused_campaign bench floor — ≥10x over the scalar "
              "campaign path (benchmarks/check_regression.py)")

    def _conversion_arg(self, node: ast.Call) -> "ast.AST | None":
        if _is_convert_call(call_name(node)) and node.args:
            return node.args[0]
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in _CONVERT_METHODS and not node.args:
            return node.func.value
        return None

    def _innermost_loop(self, func, node, chain):
        """Nearest enclosing loop of ``node`` within ``func``; a ``for``'s
        iterable and a comprehension's first source evaluate once and do
        not count as being inside that loop."""
        child = node
        for anc in chain:
            if anc is func:
                return None
            if isinstance(anc, (ast.For,)) and child is not anc.iter \
                    and child is not anc.target:
                return anc
            if isinstance(anc, ast.While):
                return anc
            if isinstance(anc, (ast.GeneratorExp, ast.ListComp,
                                ast.SetComp, ast.DictComp)) \
                    and child is not anc.generators[0].iter:
                return anc
            child = anc
        return None

    def _visit_function(self, ctx, func):
        device = _collect_device_names(func)
        if not device:
            return
        # parent chains from the local walk (framework parents exist too,
        # but the local walk already excludes nested functions)
        parents: dict = {}
        for node in _walk_function(func):
            for child in ast.iter_child_nodes(node):
                parents[id(child)] = node
        region_defs: dict = {}
        for node in _walk_function(func):
            if not isinstance(node, ast.Call):
                continue
            arg = self._conversion_arg(node)
            if arg is None:
                continue
            names = _device_names_in(arg, device)
            if not names:
                continue
            chain = []
            cur = parents.get(id(node))
            while cur is not None:
                chain.append(cur)
                cur = parents.get(id(cur))
            chain.append(func)
            loop = self._innermost_loop(func, node, chain)
            if loop is None:
                continue
            if id(loop) not in region_defs:
                region_defs[id(loop)] = _loop_region_defs(loop, device)
            escaped = names - region_defs[id(loop)]
            if not escaped:
                continue  # batched-output idiom: converted where dispatched
            yield self.finding(
                ctx, node,
                f"device→host sync in a loop body: converting "
                f"{', '.join(sorted(escaped))} (a jax value produced "
                f"outside this loop) once per iteration — dispatch once "
                f"and convert the batched output outside the loop (see "
                f"campaign._drive_group)")

    def visit_FunctionDef(self, ctx, node):
        yield from self._visit_function(ctx, node)

    def visit_AsyncFunctionDef(self, ctx, node):  # pragma: no cover
        yield from self._visit_function(ctx, node)
