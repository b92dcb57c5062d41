"""Python's full (generation-2) garbage collections, in milliseconds per
configuration recorded (``repro.gc``). Program spans, innermost wins
(``program_spans.py``)."""
import program_spans

SPANS = ("repro.gc",)


def read(run):
    return program_spans.ms_per_unit(run, SPANS)
