"""Making the live kernel's inputs (``repro.live.inputs``) and each
configuration's first call: trace, lower, compile or cache load, first
run (``repro.live.first_call``), in milliseconds per configuration
recorded. Program spans, innermost wins (``program_spans.py``)."""
import program_spans

SPANS = ("repro.live.inputs", "repro.live.first_call")


def read(run):
    return program_spans.ms_per_unit(run, SPANS)
