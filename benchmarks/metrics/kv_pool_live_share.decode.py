"""Tokens live in the paged store, averaged over the window, over the tokens
the store can hold (``engine.kv_blocks``): how much of the reserved pool the
traffic really fills."""
from harness import readers


def read(run):
    return readers.kv_pool_live_share_pct(run)
