"""Idle chip per decode step inside ``serving_decode_post``, ms: counters, the
recompile guard and the per-slot mirror loop."""
from harness import enginegaps


def read(run):
    return enginegaps.part(run, "post")
