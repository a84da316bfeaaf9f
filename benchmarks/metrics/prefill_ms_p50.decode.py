"""Device time under each ``chainermn.serving_prefill`` span, median."""
from harness import readers


def read(run):
    return readers.span_device_ms_p50(run, readers.PREFILL_SPAN)
