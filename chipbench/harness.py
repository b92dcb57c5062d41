"""What every cell of the benchmark shares: finding a cell's files by name,
holding the chip, counting compile seconds, the table of peaks, and the
result line.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix:

  configs/<config>.json   the deployment: its sizes, its source, and the
                          ``kind`` of work it is (``kinds/<kind>.py``, the
                          general generator that reads a traffic mix)
  traffic/<traffic>.json  the parameters of the work the window drives
  metrics/<metric>.py     one per-layer metric: ``read(run)`` returns its
                          value, or None where it finds nothing to read
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import re
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
WORK = os.path.join(HERE, "work")
# the persistent compile cache lives at a fixed path in the checkout, so
# that only the first run of a cell there compiles
COMPILE_CACHE = os.path.join(WORK, "jax_cache")


class NoChip(SystemExit):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One ``workloads`` entry of ``BENCHMARK.json`` with the files it
    names: its configuration and traffic under ``root``, its generator and
    metric readers beside this file."""

    def __init__(self, bench: dict, name: str, root: str = HERE):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"unknown workload {name!r}; "
                             f"known: {sorted(cells)}")
        self.spec = cells[name]
        self.name = name
        self.chips = int(self.spec["chips"])
        self.config = load_json(os.path.join(
            root, "configs", self.spec["config"] + ".json"))
        self.traffic = load_json(os.path.join(
            root, "traffic", self.spec["traffic"] + ".json"))
        self.kind = self.config["kind"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def generator(self):
        return load_module(os.path.join(HERE, "kinds", self.kind + ".py"),
                           f"chipbench_kind_{self.kind}")

    def metric_reader(self, metric: str):
        return load_module(os.path.join(HERE, "metrics", metric + ".py"),
                           "chipbench_metric_" + re.sub(r"\W", "_", metric))


def hold_chips(n: int) -> list:
    """The first ``n`` TPU chips of this host; raises ``NoChip`` where JAX
    finds another platform or fewer chips. Never falls back to the CPU."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"chipbench: needs {n} TPU chip(s); JAX found "
                     f"{devices[0].platform!r}")
    if len(devices) < n:
        raise NoChip(f"chipbench: needs {n} TPU chip(s); JAX found "
                     f"{len(devices)}")
    return devices[:n]


def peaks_for(kind: str, path: str = os.path.join(HERE, "peaks.json")
              ) -> dict:
    """The published peaks of a ``device_kind``; an unknown kind is an
    error."""
    table = load_json(path)["devices"]
    if kind not in table:
        raise SystemExit(f"chipbench: no peaks for device kind {kind!r} "
                         f"in peaks.json; known: {sorted(table)}")
    return table[kind]


class CompileClock:
    """Seconds XLA spent getting programs while the clock runs: JAX's
    ``backend_compile_duration`` events, which time a compile or, where
    the persistent cache holds the program, its load from there. A load
    is told apart by the cache's retrieval event, which comes first on the
    same thread."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"

    def __init__(self):
        import jax
        self.total = 0.0
        self.count = 0
        self.loads = 0
        self._hit = threading.local()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event == self.CACHE_LOAD:
            self._hit.flag = True
        elif event == self.EVENT:
            self.total += duration
            self.count += 1
            if getattr(self._hit, "flag", False):
                self.loads += 1
                self._hit.flag = False

    @contextlib.contextmanager
    def phase(self):
        """Yields ``[wall, seconds, programs, of them loaded from the
        persistent cache]``, filled in on exit."""
        t0, c0, n0, l0 = time.perf_counter(), self.total, self.count, \
            self.loads
        out = [0.0, 0.0, 0, 0]
        try:
            yield out
        finally:
            out[0] = time.perf_counter() - t0
            out[1] = self.total - c0
            out[2] = self.count - n0
            out[3] = self.loads - l0


def use_compile_cache() -> None:
    """Point JAX's persistent compile cache, and the program's, at the
    checkout's fixed directory unless ``JAX_COMPILATION_CACHE_DIR`` is
    already set, and keep every program there, however quick to compile.
    Call before JAX is imported."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", COMPILE_CACHE)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"


def program_path() -> None:
    """Import the program from this checkout's ``src``; a checkout without
    it is an error."""
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"chipbench: no program under {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def device_info(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def emit(result: dict, compared: list) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, and the result line, with them under ``compared``
    last, as the last line of standard output."""
    for name, value, limit in compared:
        print(f"compared {name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    result = dict(result)
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, value, limit in compared}
    print(json.dumps(result), flush=True)
