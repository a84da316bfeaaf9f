"""Device time under block_N/moe outside the expert products (router, top-k,
sort, gathers, weighted sum), per decode span."""
from harness import families


def read(run):
    return families.of(run["config"]).moe_dispatch_ms_per_step(run)
