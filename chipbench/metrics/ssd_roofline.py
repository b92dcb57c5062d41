"""The SSD scan's share of its roofline: n calls x the least time the chip
could take for one call / the summed device time of the calls (profiler
trace: programs whose name holds "ssd_scan"). The least time is the
larger of the grouped scan's operations over the bf16 peak and its bytes
(x read and y written once, B and C once per group, dt and A once) over
the HBM bandwidth (``ssd_counts.py``, ``counts.least_time``); at the
cell's shape the bytes bound it at every chunk length."""
import counts
import ssd_counts

PATTERNS = ("ssd_scan",)


def read(run):
    ex = run.trace.executions(PATTERNS, run.lo, run.hi)
    if not ex:
        return None
    p = run.config["problem"]
    flops, hbm = ssd_counts.grouped_scan(
        p["bh"], p["bh_g"], p["seq"], p["p"], p["n"], run.config["itemsize"],
        run.config["space"]["tunables"]["chunk"])
    least, _bound = counts.least_time(flops, hbm, run.peaks)
    return 100.0 * len(ex) * least / (sum(e - s for _, s, e in ex) / 1e9)
