"""The attention kernel's share of its roofline: n calls x the least time
the chip could take for one call / the summed device time of the calls
(profiler trace: programs whose name holds "flash_attention"). The least
time is the larger of the causal attention's operations over the bf16
peak and its bytes (q, k, v read once, o written once) over the HBM
bandwidth (``counts.py``); at the cell's shape the operations bound it."""
import counts

PATTERNS = ("flash_attention",)


def read(run):
    ex = run.trace.executions(PATTERNS, run.lo, run.hi)
    if not ex:
        return None
    p = run.config["problem"]
    flops, hbm = counts.causal_attention(p["bh"], p["bh_kv"], p["seq"],
                                         p["d"], run.config["itemsize"])
    least, _bound = counts.least_time(flops, hbm, run.peaks)
    return 100.0 * len(ex) * least / (sum(e - s for _, s, e in ex) / 1e9)
