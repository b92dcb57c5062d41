"""The window's host time split by the program's own spans.

The program wraps each host layer in a span named ``repro.<layer>``
(``src/repro/core/spans.py``), written on the profiler's host plane. Each
instant of the window goes to the innermost ``repro.*`` span that covers
it, on whatever thread: the one that started last. So a collection inside
stepping counts as collection, no instant counts twice, and the parts add
up to the window. Time no such span covers is ``UNATTRIBUTED``.
"""
from __future__ import annotations

import heapq

PREFIX = "repro."
UNATTRIBUTED = "unattributed"


def attribute(events, lo: float, hi: float) -> dict:
    """Nanoseconds of ``[lo, hi]`` per span name, innermost wins, and the
    rest under ``UNATTRIBUTED``; ``events`` are ``(name, start, end)``."""
    spans = sorted((max(s, lo), min(e, hi), name) for name, s, e in events
                   if name.startswith(PREFIX) and e > lo and s < hi
                   and e > s)
    cuts = sorted({lo, hi, *(t for s, e, _ in spans for t in (s, e))})
    out: dict = {UNATTRIBUTED: 0.0}
    live: list = []  # heap of (-start, end, name): latest start on top
    k = 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(spans) and spans[k][0] <= a:
            s, e, name = spans[k]
            heapq.heappush(live, (-s, e, name))
            k += 1
        while live and live[0][1] <= a:
            heapq.heappop(live)
        name = live[0][2] if live else UNATTRIBUTED
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def split(run) -> "dict | None":
    """The window's split, computed once per run; None where the program
    wrote no span (it predates them)."""
    if not hasattr(run, "_program_spans"):
        found = any(ev[0].startswith(PREFIX) for ev in run.trace.host)
        run._program_spans = attribute(run.trace.host, run.lo, run.hi) \
            if found else None
    return run._program_spans


def ms_per_unit(run, names) -> "float | None":
    """Milliseconds of the window per unit of work that the spans
    ``names`` (or ``UNATTRIBUTED``) hold."""
    parts = split(run)
    if parts is None or not run.units:
        return None
    return sum(parts.get(n, 0.0) for n in names) / 1e6 / run.units
