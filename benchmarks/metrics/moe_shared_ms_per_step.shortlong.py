"""Device time of the operations traced under block_N/moe/shared (the shared
expert every token goes through) inside decode spans, per decode span. A
family whose model has no shared expert offers no such reader: nothing to
read."""
from harness import families


def read(run):
    reader = getattr(families.of(run["config"]), "moe_shared_ms_per_step",
                     None)
    return None if reader is None else reader(run)
