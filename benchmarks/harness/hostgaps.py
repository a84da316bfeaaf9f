"""The chip's idle time between the programs of a decode step, split by what
the thread that drives the chip was doing: the arithmetic the six
``host_gap_*`` readers under ``metrics/`` share.

``FCFSScheduler.step()`` is tiled by sibling ``chainermn.serving_*`` spans
(the program's ``STEP_PHASES``), on the clock the device's operations are on.
Idle seconds of chip 0 (the complement of ``Reduced.busy_between``) inside
the spans of one name, cut to the traced window, are that phase's share of
the host gap; every share is divided by the number of
``chainermn.serving_decode`` spans that lie in the window, so all are
milliseconds per decode step, and the parts add up to the whole:

    whole = admit + deliver + account + engine + asleep + unspanned

A program without these spans (an older commit) gives the whole and no
parts: the five metrics of the parts are then left out of the line.
"""

from __future__ import annotations

from harness import readers

P = "chainermn.serving_"
DECODE, PREFILL = readers.DECODE_SPAN, readers.PREFILL_SPAN
PARTS = {
    # the scheduler before the decode call; the prefill programs the
    # admission loop holds are the engine's
    "admit": (P + "policy", P + "admit", P + "blocks"),
    "deliver": (P + "deliver", P + "flush"),
    "account": (P + "account",),
    # serving_decode holds its two children, serving_admit the prefills
    "engine": (DECODE, P + "decode_post", PREFILL),
    "asleep": (P + "idle",),
}
NEW_SPANS = (P + "policy", P + "decode_post", P + "account", P + "deliver")


def split(run: dict):
    """``{"whole", "admit", "deliver", "account", "engine", "asleep",
    "unspanned"}`` in milliseconds of idle chip per decode step; ``None``
    without a trace or without decode spans in it, and the whole alone
    where the program has no phase spans."""
    tr = readers._traced(run)
    steps = len(tr.spans(DECODE)) if tr is not None else 0
    if not steps:
        return None
    out = {"whole": tr.window_s - tr.busy_between(tr.begin, tr.end)}
    on_thread = {}
    for s, e, name, thread in tr.host:
        if name.startswith(P) and e > tr.begin and s < tr.end:
            on_thread.setdefault(thread, {}).setdefault(name, []).append(
                (max(s, tr.begin), min(e, tr.end)))
    # the engine thread is the one the decode spans lie on
    spans = max(on_thread.values(),
                key=lambda by_name: len(by_name.get(DECODE, ())))

    def idle(names) -> float:
        return sum((e - s) - tr.busy_between(s, e)
                   for n in names for s, e in spans.get(n, ()))

    if all(n in spans for n in NEW_SPANS):
        out.update((part, idle(names)) for part, names in PARTS.items())
        out["admit"] -= idle((PREFILL,))
        out["unspanned"] = out["whole"] - sum(out[p] for p in PARTS)
    return {k: v / steps * 1e3 for k, v in out.items()}


def part(run: dict, name: str):
    """One share by name, ``None`` where there is nothing to read."""
    return (split(run) or {}).get(name)
