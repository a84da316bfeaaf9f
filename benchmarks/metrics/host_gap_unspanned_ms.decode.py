"""Idle chip per decode step under no phase span and not asleep in
``serving_idle``, ms: what the tiling of ``step()`` missed, the client
loop's turn-around, and time the thread lost to other threads."""
from harness import hostgaps


def read(run):
    return hostgaps.part(run, "unspanned")
