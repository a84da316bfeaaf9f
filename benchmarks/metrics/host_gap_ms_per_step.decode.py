"""Idle chip per ``chainermn.serving_decode`` span of the traced window, ms:
the host's share of a decode step, whatever it did in it."""
from harness import hostgaps


def read(run):
    return hostgaps.part(run, "whole")
