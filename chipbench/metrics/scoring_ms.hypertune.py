"""Scoring (Eq. 3: sample times, baselines, per-run scores, aggregation),
in milliseconds per configuration scored (``repro.score``). Program
spans, innermost wins (``program_spans.py``)."""
import program_spans

SPANS = ("repro.score",)


def read(run):
    return program_spans.ms_per_unit(run, SPANS)
