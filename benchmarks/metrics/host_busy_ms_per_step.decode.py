"""The engine thread's own work per decode step, ms: the traced window less
its sleep under ``serving_idle`` and less the busy chip inside its decode and
prefill fetches; the host gap less the sleep, plus host work the chip did not
wait for."""
from harness import enginegaps


def read(run):
    return enginegaps.part(run, "host_busy")
