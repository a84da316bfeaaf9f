"""The whole training step's share of the chips' peak: model FLOPs of forward
and backward from shapes (attention included, recomputation not) times steps
over the window, the chips and 197e12."""
from harness import readers


def read(run):
    return readers.train_step_mfu_pct(run)
