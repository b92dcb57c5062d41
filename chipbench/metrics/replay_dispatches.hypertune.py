"""Device executions of the segment-replay programs per configuration
scored (profiler trace: programs whose name holds "replay")."""

PATTERNS = ("replay",)


def read(run):
    n = len(run.trace.executions(PATTERNS, run.lo, run.hi))
    return n / run.units if n and run.units else None
