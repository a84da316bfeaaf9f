"""Idle chip per decode step inside ``serving_decode`` (operands, dispatch,
fetch), ``serving_decode_post`` and ``serving_prefill``, ms: the engine's own
host work around its programs."""
from harness import hostgaps


def read(run):
    return hostgaps.part(run, "engine")
