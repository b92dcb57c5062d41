"""Share of the window in which the chip ran no operation (profiler
trace: 1 - union of device op intervals / window)."""


def read(run):
    if not run.trace.n_devices:
        return None
    return 100.0 * (1.0 - run.trace.busy_ns(run.lo, run.hi)
                    / (run.hi - run.lo))
