"""The host side of the segment-replay dispatches, their wait on the device
included, in milliseconds per configuration scored
(``repro.replay.dispatch``). Program spans, innermost wins
(``program_spans.py``)."""
import program_spans

SPANS = ("repro.replay.dispatch",)


def read(run):
    return program_spans.ms_per_unit(run, SPANS)
