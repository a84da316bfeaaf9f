"""Idle chip per decode step inside ``serving_decode_fetch`` before the last
instant the chip was busy in it, ms: the program not yet started (operands in
transfer, launch) and the pauses between its operations."""
from harness import enginegaps


def read(run):
    return enginegaps.part(run, "launch")
