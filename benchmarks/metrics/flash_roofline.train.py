"""Roofline time of causal attention forward and backward, from shapes, over
the device time of the operations that implement it."""
from harness import readers


def read(run):
    return readers.flash_roofline_pct(run)
