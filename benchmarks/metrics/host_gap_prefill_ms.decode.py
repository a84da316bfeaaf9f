"""Idle chip per decode step inside ``serving_prefill``, its children
included, ms: the host side of the prefill programs (operands and block
tables, the jit call, the fetch of the first tokens)."""
from harness import enginegaps


def read(run):
    return enginegaps.part(run, "prefill")
