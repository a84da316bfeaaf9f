"""Roofline time of decode attention (K and V rows visible per layer kind, in
the store's int8 with scales) over the device time of attention operations
under decode spans."""
from harness import families


def read(run):
    return families.of(run["config"]).paged_decode_roofline_pct(run)
