"""Roofline time of the linear-attention layers' recurrence for the prompts
prefilled in the traced stretch (the larger of the gated delta rule's own
FLOPs, 7 dk dv a token a value head whatever the chunk, and its operands
once a token with the final state once a prompt) over the device time under
block_N/gdn/recurrence inside prefill spans. A family whose model has no
such layer offers no such reader: nothing to read."""
from harness import families


def read(run):
    reader = getattr(families.of(run["config"]), "gdn_prefill_roofline_pct",
                     None)
    return None if reader is None else reader(run)
