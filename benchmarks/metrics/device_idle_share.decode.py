"""1 - union of device-op intervals over the traced window."""
from harness import readers


def read(run):
    return readers.idle_share_pct(run)
