"""Roofline time of decode attention (each live token's K and V once, in the
store's type, from the contexts the traced stretch really held) over the
device time of the operations that implement it."""
from harness import readers


def read(run):
    return readers.paged_decode_roofline_pct(run)
