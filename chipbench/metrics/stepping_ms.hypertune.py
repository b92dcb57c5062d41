"""Host strategy stepping, in milliseconds per configuration scored:
building the runs and their drivers (``repro.campaign.build``) and
stepping the strategies' ask/tell against the value table
(``repro.campaign.step``). Program spans, innermost wins
(``program_spans.py``)."""
import program_spans

SPANS = ("repro.campaign.build", "repro.campaign.step")


def read(run):
    return program_spans.ms_per_unit(run, SPANS)
