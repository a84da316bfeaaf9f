"""Live slots per ``chainermn.serving_decode`` span: tokens the traced decode
steps delivered over steps times slots."""
from harness import readers


def read(run):
    return readers.batch_occupancy_pct(run)
