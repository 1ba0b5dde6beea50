"""Mean milliseconds of ``serve.harvest`` per wave in the traced window,
less the ``runtime.wait`` inside it: the host's work on a wave once its
device work is ready (readback, estimators, routing) (program span)."""
from bench import program_spans


def read(run):
    return None if run.trace is None else program_spans.mean_ms(
        "serve.harvest", less="runtime.wait")
