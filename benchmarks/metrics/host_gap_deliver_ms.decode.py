"""Idle chip per decode step inside ``serving_deliver`` and ``serving_flush``,
ms: tokens handed to their requests, metrics, traces and ``stream_cb``."""
from harness import hostgaps


def read(run):
    return hostgaps.part(run, "deliver")
