"""Model FLOPs of every token processed in the window (active parameters a
token a layer, attention over what each layer kind sees, the head once a
sampled position) over the window and 197e12."""
from harness import families


def read(run):
    return families.of(run["config"]).step_mfu_pct(run)
