"""The six ``host_gap_*`` readers on a traced run of the small serving cell
on the CPU (the path ``Reduced`` has for a trace without device planes: a
rehearsal of the arithmetic, its numbers are no device numbers):

1. each returns a number, and the whole is the sum of its parts;
2. each returns ``None`` without a trace;
3. on a trace without the program's phase spans (an older commit) the whole
   is still read and the parts are left out, and nothing raises.
"""

import copy
import json
import os
import time
import types

import pytest

from harness import common, hostgaps, peaks, serve

DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "cgpt13b-serve-decode"
READERS = {
    "host_gap_ms_per_step.decode": "whole",
    "host_gap_admit_ms.decode": "admit",
    "host_gap_deliver_ms.decode": "deliver",
    "host_gap_account_ms.decode": "account",
    "host_gap_engine_ms.decode": "engine",
    "host_gap_unspanned_ms.decode": "unspanned",
}


def read(name, run):
    return common.load_module("metrics", name + ".py").read(run)


@pytest.fixture(scope="module")
def traced_run():
    def load(name):
        with open(os.path.join(DATA, name)) as f:
            return json.load(f)

    peaks.PEAKS.setdefault("cpu", {"flops_per_s": 1e12, "bytes_per_s": 1e11})
    cell = {"name": CELL, "config": "tiny", "traffic": "tiny", "chips": 1}
    args = types.SimpleNamespace(seed=2**31 + 9, seconds=1.5, trace=1)
    run_rec, _, checks = serve.run(
        cell, load("tiny-gpt.json"), load("tiny-chat.json"), args,
        common.require_chips(1, allow_cpu=True), time.perf_counter())
    assert all(c["ok"] for c in checks.values()), checks
    assert len(run_rec["trace"].spans(hostgaps.DECODE)) >= 3
    return run_rec


def test_benchmark_json_lists_the_six_for_the_decode_cell():
    listed = {m["name"]: m for m in common.metric_entries(CELL, "per_layer")}
    assert set(READERS) <= set(listed)
    for name in READERS:
        assert listed[name]["source"] == "program_span"
        assert listed[name]["unit"] == "ms"


@pytest.mark.parametrize("name", list(READERS))
def test_reader_returns_its_part(traced_run, name):
    value = read(name, traced_run)
    assert isinstance(value, float) and value >= -1e-9
    assert value == hostgaps.split(traced_run)[READERS[name]]


def test_the_parts_add_up_to_the_whole(traced_run):
    parts = hostgaps.split(traced_run)
    assert set(parts) == set(READERS.values()) | {"asleep"}
    assert parts["whole"] > 0
    assert parts["whole"] == pytest.approx(
        sum(v for k, v in parts.items() if k != "whole"), rel=1e-9)
    # and the whole is the idle share over the steps, as the ledger has it
    tr = traced_run["trace"]
    steps = len(tr.spans(hostgaps.DECODE))
    assert parts["whole"] == pytest.approx(
        (1.0 - tr.busy_s / tr.window_s) * tr.window_s / steps * 1e3)
    # the tiling leaves little outside it, even with a generator thread
    # and the profiler on the same few cores
    assert parts["unspanned"] < 0.5 * parts["whole"]


@pytest.mark.parametrize("name", list(READERS))
def test_reader_returns_none_without_a_trace(traced_run, name):
    assert read(name, dict(traced_run, trace=None)) is None


def test_a_program_without_the_phases_gives_the_whole_alone(traced_run):
    old = copy.copy(traced_run["trace"])
    kept = {hostgaps.DECODE, hostgaps.PREFILL, hostgaps.P + "admit"}
    old.host = [h for h in old.host
                if not h[2].startswith(hostgaps.P) or h[2] in kept]
    run = dict(traced_run, trace=old)
    assert read("host_gap_ms_per_step.decode", run) == pytest.approx(
        hostgaps.split(traced_run)["whole"])
    for name in list(READERS)[1:]:
        assert read(name, run) is None
