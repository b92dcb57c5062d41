"""The window that no program span covers (the entry points and the
harness), in milliseconds per configuration recorded. With the four other
``*_ms.record`` metrics it adds up to the window per configuration
(``program_spans.py``)."""
import program_spans

SPANS = (program_spans.UNATTRIBUTED,)


def read(run):
    return program_spans.ms_per_unit(run, SPANS)
