"""Roofline time of the linear-attention layers' recurrence for the tokens
decoded in the traced stretch (each live slot's state read once and written
once a linear layer, float32, with its q, k, v, g, beta and output) over the
device time under block_N/gdn/recurrence inside decode spans. A family whose
model has no such layer offers no such reader: nothing to read."""
from harness import families


def read(run):
    reader = getattr(families.of(run["config"]), "gdn_decode_roofline_pct",
                     None)
    return None if reader is None else reader(run)
