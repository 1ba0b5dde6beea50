"""Device milliseconds per traced whole-graph inference of the operations
under the program's ``attention`` name scope (device trace)."""


def read(run):
    s = run.record.get("attention_s")
    return None if s is None else s * 1e3
