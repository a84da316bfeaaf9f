"""Roofline time of the expert products (every assignment's FLOPs, the weights
of the experts hit once a program) over the device time under
block_N/moe/experts."""
from harness import families


def read(run):
    return families.of(run["config"]).moe_roofline_pct(run)
