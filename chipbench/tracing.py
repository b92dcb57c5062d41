"""Reduce a JAX profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy time, executions of named device programs, the
host spans the harness wrote, and the idle gaps between device work.

All times are nanoseconds on the profiler's clock, which it shares between
the host and the device planes.
"""
from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:"
HOST_PLANE = "/host:CPU"
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "chipbench."


def union_ns(intervals) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered_ns(intervals) -> float:
    return float(sum(e - s for s, e in union_ns(intervals)))


def gaps(busy, lo: float, hi: float) -> list:
    """The idle ``(start, end)`` stretches of ``[lo, hi]`` that ``busy``
    (disjoint, sorted) leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def matches(name: str, patterns) -> bool:
    """Case-insensitive substring match of an event name."""
    low = name.lower()
    return any(p.lower() in low for p in patterns)


class Trace:
    """The events of one trace, split into device and host events."""

    def __init__(self, planes):
        # per device plane: its op events and its program executions,
        # each (name, start_ns, end_ns)
        self.device_ops: dict = {}
        self.device_modules: dict = {}
        self.host: list = []
        for plane in planes:
            name = plane["name"]
            if name.startswith(DEVICE_PREFIX):
                ops = self.device_ops.setdefault(name, [])
                mods = self.device_modules.setdefault(name, [])
                for line in plane["lines"]:
                    if line["name"] == OPS_LINE:
                        ops.extend(line["events"])
                    elif line["name"] == MODULES_LINE:
                        mods.extend(line["events"])
            elif name == HOST_PLANE:
                for line in plane["lines"]:
                    self.host.extend(line["events"])
        # planes with programs but no op line count their programs as busy
        for name, ops in self.device_ops.items():
            if not ops:
                ops.extend(self.device_modules[name])

    @classmethod
    def load(cls, trace_dir: str) -> "Trace":
        """Read the newest ``.xplane.pb`` under ``trace_dir``."""
        from jax.profiler import ProfileData
        files = sorted(glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
            key=os.path.getmtime)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
        data = ProfileData.from_file(files[-1])
        planes = []
        for plane in data.planes:
            lines = []
            for line in plane.lines:
                lines.append({"name": line.name, "events": [
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events]})
            planes.append({"name": plane.name, "lines": lines})
        return cls(planes)

    # ----------------------------------------------------------- host spans
    def spans(self, prefix: str = SPAN_PREFIX) -> list:
        """The harness's own host spans, ``(name, start, end)``."""
        return sorted((ev for ev in self.host if ev[0].startswith(prefix)),
                      key=lambda ev: ev[1])

    def window(self, name: str) -> tuple:
        """``(start, end)`` of the first host span named ``name``."""
        for ev in self.spans():
            if ev[0] == name:
                return ev[1], ev[2]
        raise KeyError(f"no span {name!r} in the trace")

    # --------------------------------------------------------------- device
    @property
    def n_devices(self) -> int:
        return sum(1 for ops in self.device_ops.values() if ops)

    def busy_ns(self, lo: float, hi: float) -> float:
        """Device-busy nanoseconds within ``[lo, hi]``, averaged over the
        devices that ran anything."""
        per = [covered_ns(clip([(s, e) for _, s, e in ops], lo, hi))
               for ops in self.device_ops.values() if ops]
        return sum(per) / len(per) if per else 0.0

    def executions(self, patterns, lo: float, hi: float) -> list:
        """Device program executions whose name matches, started within
        ``[lo, hi]``, on every device: ``(name, start, end)``."""
        return [ev for mods in self.device_modules.values() for ev in mods
                if lo <= ev[1] < hi and matches(ev[0], patterns)]

    def top_ops(self, lo: float, hi: float, n: int = 10) -> list:
        """The device operations that took most time in ``[lo, hi]``:
        ``[name, seconds]``, summed over executions, averaged over
        devices."""
        tot: dict = {}
        devs = [ops for ops in self.device_ops.values() if ops]
        for ops in devs:
            for name, s, e in clip_events(ops, lo, hi):
                tot[name] = tot.get(name, 0.0) + (e - s)
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v / 1e9 / len(devs)] for k, v in ranked]

    def idle_gaps(self, lo: float, hi: float, n: int = 10) -> list:
        """The longest stretches of ``[lo, hi]`` in which the first busy
        device ran nothing, named by what the host was doing at their
        middle: the harness span, then the shortest other host event that
        covers it. ``[label, seconds]``."""
        devs = [ops for ops in self.device_ops.values() if ops]
        if not devs:
            return []
        busy = union_ns(clip([(s, e) for _, s, e in devs[0]], lo, hi))
        idle = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:n]
        spans = self.spans()
        out = []
        for s, e in idle:
            mid = 0.5 * (s + e)
            inner = [ev for ev in spans if ev[1] <= mid <= ev[2]]
            label = min(inner, key=lambda ev: ev[2] - ev[1])[0] \
                if inner else "outside the harness's spans"
            other = [ev for ev in self.host
                     if ev[1] <= mid <= ev[2] and ev[2] > ev[1]
                     and not ev[0].startswith(SPAN_PREFIX)]
            if other:
                label += " > " + min(other, key=lambda ev: ev[2] - ev[1])[0]
            out.append([label, (e - s) / 1e9])
        return out


def clip_events(events, lo: float, hi: float) -> list:
    return [(name, max(s, lo), min(e, hi)) for name, s, e in events
            if e > lo and s < hi]
