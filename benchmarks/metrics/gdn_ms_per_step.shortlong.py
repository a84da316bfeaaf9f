"""Device time of the operations traced under block_N/gdn (the
linear-attention layers' projections, convolution, recurrence and gated
norm) inside decode spans, per decode span. A family whose model has no such
layer offers no such reader: nothing to read."""
from harness import families


def read(run):
    reader = getattr(families.of(run["config"]), "gdn_ms_per_step", None)
    return None if reader is None else reader(run)
