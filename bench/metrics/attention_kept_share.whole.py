"""Percent of the attention's support entries that kept a weight above
zero, over the traced window's inferences (``attention.kept`` over
``attention.support``, program counters)."""
from bench import program_spans


def read(run):
    if run.trace is None:
        return None
    share = program_spans.ratio("attention.kept", "attention.support")
    return None if share is None else 100.0 * share
