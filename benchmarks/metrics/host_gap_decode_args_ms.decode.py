"""Idle chip per decode step inside ``serving_decode_args``, ms: the decode
program's operands built and put on the device."""
from harness import enginegaps


def read(run):
    return enginegaps.part(run, "args")
