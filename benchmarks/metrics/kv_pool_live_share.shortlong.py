"""Bytes live in both block stores (all tokens in full layers, at most the
window's in window layers), averaged over the window, over both pools."""
from harness import families


def read(run):
    return families.of(run["config"]).kv_pool_live_share_pct(run)
