"""Device milliseconds of the segment-replay programs per configuration
scored (profiler trace: summed durations of programs whose name holds
"replay")."""

PATTERNS = ("replay",)


def read(run):
    ex = run.trace.executions(PATTERNS, run.lo, run.hi)
    if not ex or not run.units:
        return None
    return sum(e - s for _, s, e in ex) / 1e6 / run.units
