"""The timed kernel calls and their host overhead (``repro.live.timed``),
in milliseconds per configuration recorded. Program spans, innermost
wins (``program_spans.py``)."""
import program_spans

SPANS = ("repro.live.timed",)


def read(run):
    return program_spans.ms_per_unit(run, SPANS)
