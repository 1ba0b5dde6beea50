"""Mean milliseconds a request waited in the scheduler's queue, from its
submission to the launch of its wave, over the requests launched in the
traced window (``serve.queue_wait_s`` over ``serve.requests_launched``,
program counters)."""
from bench import program_spans


def read(run):
    if run.trace is None:
        return None
    s = program_spans.ratio("serve.queue_wait_s", "serve.requests_launched")
    return None if s is None else s * 1e3
