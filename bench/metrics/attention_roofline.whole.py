"""Percent of the HBM roofline that the attention reaches: the bytes it
needs on the support (``attention_bytes`` of the model module) over its
device time per inference, over the chip's HBM bandwidth (device trace)."""


def read(run):
    s, b = run.record.get("attention_s"), run.record.get("attention_bytes")
    if not s or not b:
        return None
    return 100.0 * b / (s * run.peaks["hbm_bytes_per_s"])
