"""Mean milliseconds of ``runtime.report`` per traced whole-graph
inference: the executor's host work once the program's outputs are ready
(densities, code readback, per-kernel bookkeeping) (program span)."""
from bench import program_spans


def read(run):
    return None if run.trace is None else program_spans.mean_ms(
        "runtime.report")
