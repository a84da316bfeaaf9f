"""The whole serving step's share of the chip's peak: model FLOPs of every
token processed in the window over the window and 197e12."""
from harness import readers


def read(run):
    return readers.serve_step_mfu_pct(run)
