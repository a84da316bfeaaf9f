"""Idle chip per decode step inside ``serving_decode_dispatch``, ms: the jit
call of the decode program (its operands' flattening and the enqueue)."""
from harness import enginegaps


def read(run):
    return enginegaps.part(run, "dispatch")
