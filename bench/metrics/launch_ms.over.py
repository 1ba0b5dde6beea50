"""Mean milliseconds of ``serve.launch`` per wave in the traced window:
slot layout, slot fill and the executor's dispatch (program span)."""
from bench import program_spans


def read(run):
    return None if run.trace is None else program_spans.mean_ms(
        "serve.launch")
