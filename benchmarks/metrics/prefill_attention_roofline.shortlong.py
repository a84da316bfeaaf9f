"""Roofline time of prefill attention (visible pairs only, a prompt's K and V
read once and written once to the store) over the device time of attention
operations under prefill spans."""
from harness import families


def read(run):
    return families.of(run["config"]).prefill_attention_roofline_pct(run)
