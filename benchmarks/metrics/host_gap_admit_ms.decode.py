"""Idle chip per decode step inside ``serving_policy``, ``serving_admit`` (less
its ``serving_prefill`` children) and ``serving_blocks``, ms: the scheduler
before the decode call."""
from harness import hostgaps


def read(run):
    return hostgaps.part(run, "admit")
