"""Host spans at the program's layer boundaries, on the JAX profiler's clock.

``span(name)`` is a ``jax.profiler.TraceAnnotation`` while a profiler
session records, so the span lands on the profiler's host plane, on the
same clock as the device trace, and every stretch in which the device
waits can be put down to a layer of the program. With no session it is
one shared null context, well under a microsecond per span. Spans
carry no metadata and never synchronise with the device; a count of the
work a layer did is a count of its spans (each replay dispatch, each
full collection, each first call of a live kernel is one).

Nothing here imports JAX: a process that has not imported it runs no
profiler session, so the modules that work without JAX stay free of it.

Importing this module installs one ``gc.callbacks`` hook that wraps each
generation-2 collection in a ``repro.gc`` span while a session records.
"""
from __future__ import annotations

import contextlib
import gc
import sys

# the span names, one per layer boundary (docs/performance.md, "Tracing a
# campaign or a recording")
CAMPAIGN_BUILD = "repro.campaign.build"
CAMPAIGN_STEP = "repro.campaign.step"
REPLAY_DISPATCH = "repro.replay.dispatch"
SCORE = "repro.score"
LIVE_INPUTS = "repro.live.inputs"
LIVE_FIRST_CALL = "repro.live.first_call"
LIVE_TIMED = "repro.live.timed"
JOURNAL_APPEND = "repro.journal.append"
RECORD_MERGE = "repro.record.merge"
GC = "repro.gc"

_NULL = contextlib.nullcontext()
# jax.profiler.TraceAnnotation and its "is a session recording" check,
# found once JAX has been imported
_annotation = None
_recording = None


def _always() -> bool:
    return True


def _bind() -> bool:
    """Find the profiler's annotation among the imported modules; import
    nothing (the collector's hook may run while JAX is being imported)."""
    global _annotation, _recording
    annotation = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation",
                         None)
    if annotation is None:
        return False
    # TraceMe.is_enabled: true while a session records; without it, build
    # the annotation always (it records nothing outside a session)
    _recording = getattr(annotation, "is_enabled", None) or _always
    _annotation = annotation
    return True


def span(name: str):
    """A context manager that records ``name`` as a host span while a JAX
    profiler session records, and does nothing otherwise."""
    if (_annotation is not None or _bind()) and _recording():
        return _annotation(name)
    return _NULL


_gc_span = None


def _on_gc(phase: str, info: dict) -> None:
    global _gc_span
    if info["generation"] != 2:
        return
    if phase == "start":
        if (_annotation is not None or _bind()) and _recording():
            _gc_span = _annotation(GC)
            _gc_span.__enter__()
    elif _gc_span is not None:
        _gc_span.__exit__(None, None, None)
        _gc_span = None


if not any(getattr(cb, "__qualname__", None) == _on_gc.__qualname__
           and getattr(cb, "__module__", None) == __name__
           for cb in gc.callbacks):
    gc.callbacks.append(_on_gc)
