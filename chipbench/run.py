#!/usr/bin/env python3
"""Run one cell of the chip benchmark (``BENCHMARK.json``).

    python3 chipbench/run.py --workload NAME --seed N --seconds S --trace 0|1

From the root of a checkout, on a machine with the TPU chips the cell asks
for; anywhere else it exits non-zero and prints no result. One process:
set-up (counted in ``setup_s``, from the start of this process), a window
of ``--seconds`` that ends at the first completed unit of work after its
deadline, then the check of what the window produced against the plain
reference. The last line of standard output is the result as one JSON
object. With ``--trace 1`` the window runs under the JAX profiler and the
result holds the cell's per-layer metrics instead of its end-to-end ones.

``--control NAME`` puts the reference in a lower precision in the
program's place for the check; the benchmark's own runs never pass it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import harness  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default=None)
    return ap.parse_args(argv)


class Run:
    """What a per-layer metric reads: the window's trace and counts."""

    def __init__(self, cell, trace, window_s, units, compile_s, peaks):
        self.config = cell.config
        self.trace = trace
        self.window_s = window_s       # host clock
        self.units = units
        self.compile_s = compile_s
        self.peaks = peaks
        self.lo, self.hi = trace.window("chipbench.window")
        self.trace_window_s = (self.hi - self.lo) / 1e9


def measure(cell, seed: int, seconds: float, trace: bool, control=None,
            devices=None, log=sys.stdout) -> tuple:
    """Set up, run the window, check. Returns ``(result, compared)``."""
    import jax
    devices = devices if devices is not None else \
        harness.hold_chips(cell.chips)
    peaks = harness.peaks_for(devices[0].device_kind)
    clock = harness.CompileClock()
    gen = cell.generator().Generator(cell, seed, log=log)
    with clock.phase() as setup_c, \
            jax.profiler.TraceAnnotation("chipbench.setup"):
        gen.setup()
    print(f"setup: {setup_c[0]:.3f} s, {setup_c[1]:.3f} s compiling or "
          f"loading {setup_c[2]} programs ({setup_c[3]} from the persistent "
          f"cache)", file=log, flush=True)
    trace_dir = os.path.join(harness.WORK, "trace", cell.name)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        # without the Python tracer: it slows host work about threefold and
        # its events made a traced campaign run take 300 s; the harness's
        # own spans and JAX's host events still name the idle gaps
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    t0 = time.perf_counter()
    setup_s = t0 - T_START
    units, ends, met = 0, [], [clock.count]
    with clock.phase() as win_c, \
            jax.profiler.TraceAnnotation("chipbench.window"):
        while True:
            units += gen.step()
            ends.append(time.perf_counter() - t0)
            met.append(clock.count)
            if ends[-1] >= seconds:
                break
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    print(f"window: {units} units in {window_s:.3f} s, {win_c[1]:.3f} s "
          f"compiling or loading {win_c[2]} programs ({win_c[3]} from the "
          f"persistent cache); steps ended at "
          f"{[round(t, 3) for t in ends]} s, programs met per step "
          f"{[b - a for a, b in zip(met, met[1:])]}", file=log, flush=True)
    device = harness.device_info(devices)
    with jax.profiler.TraceAnnotation("chipbench.check"):
        correct, compared, attempted, failed = gen.check(control) \
            if control else gen.check()
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": {}, "device": device}
    if not trace:
        values = {"setup_s": setup_s, gen.rate: units / window_s}
        for m in cell.end_to_end:
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
        return result, compared
    from tracing import Trace
    tr = Trace.load(trace_dir)
    run = Run(cell, tr, window_s, units, win_c[1], peaks)
    for m in cell.per_layer:
        value = cell.metric_reader(m["name"]).read(run)
        if value is not None:
            result["metrics"][m["name"]] = {"value": value,
                                            "unit": m["unit"]}
    device["busy_s"] = tr.busy_ns(run.lo, run.hi) / 1e9
    device["window_s"] = run.trace_window_s
    result["breakdown"] = {"device_ops": tr.top_ops(run.lo, run.hi),
                           "idle_gaps": tr.idle_gaps(run.lo, run.hi)}
    shutil.rmtree(trace_dir, ignore_errors=True)
    return result, compared


def main(argv=None) -> int:
    args = parse(argv)
    harness.use_compile_cache()
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = harness.Cell(bench, args.workload)
    harness.program_path()
    result, compared = measure(cell, args.seed, args.seconds,
                               bool(args.trace), args.control)
    harness.emit(result, compared)
    return 0


if __name__ == "__main__":
    sys.exit(main())
