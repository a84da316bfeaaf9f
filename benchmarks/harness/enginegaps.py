"""The engine's share of the host gap (``host_gap_engine_ms.decode``), split
where the thread that drives the chip spends it, and that thread's own work
a step: the arithmetic of the seven readers ``host_gap_decode_*_ms``,
``host_gap_prefill_ms`` and ``host_busy_ms_per_step`` (``.decode``) under
``metrics/``, on ``hostgaps``' terms.

The engine opens children inside its phase spans (the program's
``STEP_PHASE_CHILDREN``): ``serving_decode`` holds ``_args`` (operands and
their puts), ``_dispatch`` (the jit call) and ``_fetch`` (the wait for the
tokens and their copy home); ``serving_prefill`` holds ``_args``,
``_dispatch`` and ``_fetch`` likewise. The chip's idle time inside a decode
fetch is cut at the last instant the chip was busy in it: before it the
program had not started, or paused between operations (``launch``); after
it the host was waking up and copying back (``wake``; all of the fetch's
idle time where no operation ran in it). Every part is milliseconds of idle
chip per decode step, counted on the engine thread as ``hostgaps.split``
counts them, so that

    args + dispatch + launch + wake + post + prefill + decode_self = engine

where ``decode_self`` is the idle chip inside ``serving_decode`` outside its
three children (the watchdog, the fault cut-point, the span's counts).

``host_busy`` is the engine thread's own work a decode step: the window,
less its sleep under ``serving_idle``, less the busy chip inside its decode
and prefill fetches, where it waits on its own program. It is the host gap
less the sleep's idle chip, plus host work the chip did not wait for.

A program without ``serving_decode_dispatch`` spans (an older commit) gives
none of these: its prefill waits have no span and would count as work.
"""

from __future__ import annotations

import bisect

from harness import hostgaps, readers, trace

P, DECODE, PREFILL = hostgaps.P, hostgaps.DECODE, hostgaps.PREFILL
ARGS, DISPATCH, FETCH = (DECODE + s for s in ("_args", "_dispatch", "_fetch"))
POST, IDLE = P + "decode_post", P + "idle"
PREFILL_FETCH = PREFILL + "_fetch"
PARTS = ("args", "dispatch", "launch", "wake", "post", "prefill",
         "decode_self", "host_busy")


def _engine_spans(tr) -> dict:
    """``{name: [(start, end)]}`` of the ``chainermn.serving_*`` spans on the
    thread the decode spans lie on, cut to the traced window."""
    on_thread = {}
    for s, e, name, thread in tr.host:
        if name.startswith(P) and e > tr.begin and s < tr.end:
            on_thread.setdefault(thread, {}).setdefault(name, []).append(
                (max(s, tr.begin), min(e, tr.end)))
    return max(on_thread.values(),
               key=lambda by_name: len(by_name.get(DECODE, ())))


def split(run: dict):
    """``{part: ms per decode step}`` for ``PARTS``; ``None`` without a
    trace, without decode spans in it, or without the engine's dispatch
    spans."""
    tr = readers._traced(run)
    steps = len(tr.spans(DECODE)) if tr is not None else 0
    if not steps:
        return None
    spans = _engine_spans(tr)
    if DISPATCH not in spans:
        return None
    # chip 0's busy stretches, disjoint and in order
    busy = trace.merged(tr.ops_between(lambda *_: True))
    starts = [s for s, _ in busy]

    def idle(s: float, e: float) -> float:
        return (e - s) - tr.busy_between(s, e)

    # a decode span open when the profiler stops is never written, but
    # its children that closed before are: count only children whose
    # parent the trace holds
    decodes = sorted(spans[DECODE])
    opened = [s for s, _ in decodes]
    for name in (ARGS, DISPATCH, FETCH):
        spans[name] = [
            (s, e) for s, e in spans.get(name, ())
            if (i := bisect.bisect_right(opened, s) - 1) >= 0
            and decodes[i][1] >= e]

    def idle_in(name: str) -> float:
        return sum(idle(s, e) for s, e in spans.get(name, ()))

    launch = wake = 0.0
    for s, e in spans[FETCH]:
        i = bisect.bisect_left(starts, e) - 1   # the last one begun by e
        cut = min(busy[i][1], e) if i >= 0 and busy[i][1] > s else s
        launch += idle(s, cut)
        wake += e - cut
    out = {"args": idle_in(ARGS), "dispatch": idle_in(DISPATCH),
           "launch": launch, "wake": wake, "post": idle_in(POST),
           "prefill": idle_in(PREFILL)}
    out["decode_self"] = idle_in(DECODE) - sum(
        out[p] for p in ("args", "dispatch", "launch", "wake"))
    out["host_busy"] = tr.window_s - sum(
        e - s for s, e in spans.get(IDLE, ())) - sum(
        tr.busy_between(s, e)
        for name in (FETCH, PREFILL_FETCH) for s, e in spans.get(name, ()))
    return {k: v / steps * 1e3 for k, v in out.items()}


def part(run: dict, name: str):
    """One part by name, ``None`` where there is nothing to read."""
    return (split(run) or {}).get(name)
