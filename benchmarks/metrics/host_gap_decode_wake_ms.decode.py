"""Idle chip per decode step inside ``serving_decode_fetch`` after the last
instant the chip was busy in it, ms: the host waking up and copying the
tokens back (all of the fetch's idle time where no operation ran in it)."""
from harness import enginegaps


def read(run):
    return enginegaps.part(run, "wake")
