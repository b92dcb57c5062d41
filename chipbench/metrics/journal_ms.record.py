"""Journaling: each fsync'd shard append (``repro.journal.append``) and the
merge and save of the recording (``repro.record.merge``), in
milliseconds per configuration recorded. Program spans, innermost wins
(``program_spans.py``)."""
import program_spans

SPANS = ("repro.journal.append", "repro.record.merge")


def read(run):
    return program_spans.ms_per_unit(run, SPANS)
