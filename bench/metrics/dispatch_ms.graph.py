"""Mean milliseconds of ``runtime.dispatch`` per traced whole-graph
inference: program lookup, input profiles and the call that enqueues the
program (program span)."""
from bench import program_spans


def read(run):
    return None if run.trace is None else program_spans.mean_ms(
        "runtime.dispatch")
