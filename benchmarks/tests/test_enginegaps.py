"""The seven readers of the engine's split on a traced run of the small
serving cell on the CPU (``test_hostgaps``' rehearsal: its numbers are no
device numbers), and the cut of a fetch on a trace made by hand:

1. each returns a number, and the engine's parts add up to
   ``host_gap_engine_ms.decode``;
2. each returns ``None`` without a trace, and on a trace without the
   engine's new spans (an older commit);
3. ``host_busy_ms_per_step.decode`` holds the host gap less the sleep.
"""

import copy

import pytest

from harness import common, enginegaps, hostgaps, trace
from test_hostgaps import traced_run  # noqa: F401 - the fixture

SERVING = ("cgpt13b-serve-decode", "st21b-l8-serve-short-long",
           "laguna-l5-ep2-serve-short-long",
           "qwen3next-l4-ep2-serve-short-long")
READERS = {
    "host_gap_decode_args_ms.decode": "args",
    "host_gap_decode_dispatch_ms.decode": "dispatch",
    "host_gap_decode_launch_ms.decode": "launch",
    "host_gap_decode_wake_ms.decode": "wake",
    "host_gap_decode_post_ms.decode": "post",
    "host_gap_prefill_ms.decode": "prefill",
    "host_busy_ms_per_step.decode": "host_busy",
}
NEW_SPANS = {enginegaps.DISPATCH} | {
    enginegaps.PREFILL + s for s in ("_args", "_dispatch", "_fetch")}


def read(name, run):
    return common.load_module("metrics", name + ".py").read(run)


@pytest.mark.parametrize("name", list(READERS))
def test_benchmark_json_lists_it_for_the_four_serving_cells(name):
    for cell in SERVING:
        listed = {m["name"]: m
                  for m in common.metric_entries(cell, "per_layer")}
        entry = listed[name]
        assert (entry["source"], entry["unit"], entry["better"],
                entry["moves"]) == ("program_span", "ms", "lower",
                                    "serve_tokens_per_s")
        assert tuple(entry["workloads"]) == SERVING


@pytest.mark.parametrize("name", list(READERS))
def test_reader_returns_its_part(traced_run, name):  # noqa: F811
    value = read(name, traced_run)
    assert isinstance(value, float) and value >= 0
    assert value == enginegaps.split(traced_run)[READERS[name]]


@pytest.mark.parametrize("name", list(READERS))
def test_reader_returns_none_without_a_trace(traced_run, name):  # noqa: F811
    assert read(name, dict(traced_run, trace=None)) is None


@pytest.mark.parametrize("name", list(READERS))
def test_an_older_program_gives_none(traced_run, name):  # noqa: F811
    old = copy.copy(traced_run["trace"])
    old.host = [h for h in old.host if h[2] not in NEW_SPANS]
    run = dict(traced_run, trace=old)
    assert read(name, run) is None
    # what hostgaps reads is still there
    assert hostgaps.split(run)["engine"] == pytest.approx(
        hostgaps.split(traced_run)["engine"])


def test_the_parts_add_up_to_the_engine(traced_run):  # noqa: F811
    parts = enginegaps.split(traced_run)
    assert set(parts) == set(enginegaps.PARTS)
    assert parts["decode_self"] >= -1e-9
    assert hostgaps.split(traced_run)["engine"] == pytest.approx(
        sum(v for k, v in parts.items() if k != "host_busy"), rel=1e-9)


def test_host_busy_holds_the_gap_less_the_sleep(traced_run):  # noqa: F811
    gaps = hostgaps.split(traced_run)
    assert enginegaps.part(traced_run, "host_busy") >= (
        gaps["whole"] - gaps["asleep"] - 1e-9)


def hand_made(host, busy):
    """A ``Reduced`` of a window [0, 21) whose chip 0 ran ``busy``."""
    tr = trace.Reduced.__new__(trace.Reduced)
    tr.begin, tr.end, tr.window_s, tr.offset = 0.0, 21.0, 21.0, 0.0
    tr.host = [(s, e, hostgaps.P + n, "engine") for s, e, n in host]
    tr.devices = {0: [(s, e, "op", "", "c") for s, e in busy]}
    tr._own = {0: [e - s for s, e in busy]}
    tr._busy = {0: trace.merged(busy)}
    return {"trace": tr}


def test_a_fetch_is_cut_at_its_last_busy_instant():
    # two decode steps; the first one's program runs [3, 5) and [6, 7)
    # inside its fetch [2, 8), and half a unit of work overlaps its
    # dispatch; the second one's fetch [14, 20) holds no operation
    host = [(0, 8, "decode"), (0, 1, "decode_args"),
            (1, 2, "decode_dispatch"), (2, 8, "decode_fetch"),
            (8, 10, "decode_post"), (10, 12, "idle"),
            (12, 20, "decode"), (12, 13, "decode_args"),
            (13, 14, "decode_dispatch"), (14, 20, "decode_fetch"),
            # the profiler stopped inside a third step: its decode span was
            # not written, the children that closed before it were
            (20.2, 20.5, "decode_args"), (20.5, 20.9, "decode_dispatch")]
    run = hand_made(host, [(1.5, 2.5), (3, 5), (6, 7)])
    per_step = {k: v / 1e3 * 2 for k, v in enginegaps.split(run).items()}
    assert per_step == pytest.approx({
        "args": 2, "dispatch": 1.5, "launch": 1.5, "wake": 1 + 6,
        "post": 2, "prefill": 0, "decode_self": 0,
        # the window less the sleep, less 3.5 busy inside the fetches
        "host_busy": 21 - 2 - 3.5})
    # the host gap less the sleep, plus the half unit of work the chip ran
    # while the host dispatched
    whole = hostgaps.split(run)["whole"] / 1e3 * 2
    assert whole == 21 - 4
    assert per_step["host_busy"] == pytest.approx(whole - 2 + 0.5)
