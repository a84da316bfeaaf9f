"""Bench-trajectory diff tool (ISSUE 15 satellite): direction
inference, record flattening, band building, regression/improvement
verdicts, the lint-hook staleness check — against synthetic rounds in a
tmp repo — plus the committed ``BENCH_TRAJECTORY.json`` itself, which
must pass the same check the lint hook runs."""

import importlib.util
import json
import os
import pathlib

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]

_spec = importlib.util.spec_from_file_location(
    "bench_compare", REPO / "scripts" / "bench_compare.py")
bc = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bc)


def _round(n, *, rc=0, parsed=None):
    return {"n": n, "cmd": "python bench.py", "rc": rc, "tail": "",
            "parsed": parsed}


def _rec(value, *, ttft=2.0, kind="tpu-v4"):
    return {"value": value, "device_kind": kind, "n_devices": 4,
            "serving": {"ttft_p50_ms": ttft, "tokens_per_sec": value},
            "ok": True, "label": "x"}


def _write_rounds(repo, parsed_list):
    for i, parsed in enumerate(parsed_list, start=1):
        rc = 0 if parsed is not None else 1
        (repo / f"BENCH_r{i:02d}.json").write_text(
            json.dumps(_round(i, rc=rc, parsed=parsed)))


# --------------------------------------------------------------------- #
# primitives                                                             #
# --------------------------------------------------------------------- #


def test_direction_inference():
    assert bc.direction("serving.tokens_per_sec") == "higher"
    assert bc.direction("serving.ttft_p50_ms") == "lower"
    assert bc.direction("prefix.ttft_p50_speedup") == "higher"  # not a ttft
    assert bc.direction("spec.wall_s") == "lower"
    assert bc.direction("value") == "higher"
    assert bc.direction("n_devices") is None          # informational


def test_flatten_numeric_leaves_only():
    flat = bc.flatten({"a": 1, "b": {"c": 2.5, "d": "x", "e": True},
                       "monitor": {"noise": 9}, "f": [1, 2]})
    assert flat == {"a": 1.0, "b.c": 2.5}             # skip-key + non-scalars


def test_load_rounds_normalizes_failures(tmp_path):
    _write_rounds(tmp_path, [_rec(100.0), None, {"value": None}])
    rounds = bc.load_rounds(str(tmp_path))
    assert [r["rc"] for r in rounds] == [0, 1, 0]
    assert rounds[0]["metrics"]["serving.tokens_per_sec"] == 100.0
    assert rounds[1]["metrics"] is None               # no parseable record
    assert rounds[2]["metrics"] is None               # value: None


# --------------------------------------------------------------------- #
# build + compare                                                        #
# --------------------------------------------------------------------- #


def test_build_bands_group_by_device_kind(tmp_path):
    _write_rounds(tmp_path, [_rec(100.0), _rec(120.0),
                             _rec(50.0, kind="cpu")])
    traj = bc.build_trajectory(str(tmp_path))
    assert set(traj["bands"]) == {"tpu-v4", "cpu"}
    band = traj["bands"]["tpu-v4"]["serving.tokens_per_sec"]
    assert band == {"last": 120.0, "min": 100.0, "max": 120.0, "n": 2,
                    "direction": "higher"}
    # the cpu round never pollutes the tpu bands
    assert traj["bands"]["cpu"]["value"]["n"] == 1


def test_compare_verdicts_regression_improvement_and_new(tmp_path):
    _write_rounds(tmp_path, [_rec(100.0, ttft=2.0)])
    traj = bc.build_trajectory(str(tmp_path), tolerance=0.25)
    # inside the band: ok
    v = bc.compare(bc.flatten(_rec(90.0, ttft=2.2)), "tpu-v4", traj)
    assert v["ok"] and v["checked"] > 0 and not v["regressed"]
    # throughput collapsed + latency blew up: both named
    v = bc.compare(bc.flatten(_rec(50.0, ttft=9.0)), "tpu-v4", traj)
    assert not v["ok"]
    names = {r["metric"] for r in v["regressed"]}
    assert "serving.ttft_p50_ms" in names
    assert "serving.tokens_per_sec" in names and "value" in names
    # big wins are reported as improvements, never failures
    v = bc.compare(bc.flatten(_rec(200.0, ttft=0.5)), "tpu-v4", traj)
    assert v["ok"] and len(v["improved"]) >= 2
    # unknown device kind: nothing to check against, everything "new"
    v = bc.compare(bc.flatten(_rec(1.0)), "gpu", traj)
    assert v["ok"] and v["checked"] == 0 and v["new"]


# --------------------------------------------------------------------- #
# the lint hook (--check) + CLI                                          #
# --------------------------------------------------------------------- #


def test_check_trajectory_staleness_and_banding(tmp_path):
    repo = str(tmp_path)
    # no trajectory at all
    ok, msg = bc.check_trajectory(repo)
    assert not ok and "missing" in msg
    # one successful round: consistent but nothing to band against
    _write_rounds(tmp_path, [_rec(100.0), None])
    assert bc.main(["--repo", repo, "--build"]) == 0
    ok, msg = bc.check_trajectory(repo)
    assert ok and "nothing to band against" in msg
    # second success inside tolerance: banded and green
    (tmp_path / "BENCH_r03.json").write_text(
        json.dumps(_round(3, parsed=_rec(110.0))))
    assert bc.main(["--repo", repo, "--build"]) == 0
    ok, msg = bc.check_trajectory(repo)
    assert ok and "inside tolerance" in msg
    # a regressed newest round fails the hook
    (tmp_path / "BENCH_r04.json").write_text(
        json.dumps(_round(4, parsed=_rec(10.0))))
    assert bc.main(["--repo", repo, "--build"]) == 0
    ok, msg = bc.check_trajectory(repo)
    assert not ok and "regressed" in msg
    # stale trajectory (rounds changed after --build) fails loudly
    os.remove(tmp_path / "BENCH_r04.json")
    ok, msg = bc.check_trajectory(repo)
    assert not ok and "stale" in msg


def test_record_mode_prints_parseable_verdict(tmp_path, capsys):
    repo = str(tmp_path)
    _write_rounds(tmp_path, [_rec(100.0)])
    bc.main(["--repo", repo, "--build"])
    capsys.readouterr()
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(_rec(95.0)))
    assert bc.main(["--repo", repo, "--record", str(fresh)]) == 0
    out = capsys.readouterr().out
    verdict = json.loads(out.strip().splitlines()[-1])["bench_compare"]
    assert verdict["ok"] and verdict["device_kind"] == "tpu-v4"
    # a round wrapper is unwrapped to its parsed record
    fresh.write_text(json.dumps(_round(9, parsed=_rec(10.0))))
    assert bc.main(["--repo", repo, "--record", str(fresh)]) == 1


def test_committed_trajectory_is_current():
    """The repo's own artifact passes the exact check scripts/lint.sh
    runs — if this fails, re-run bench_compare.py --build and commit."""
    if not (REPO / bc.TRAJECTORY).exists():
        pytest.skip("no committed trajectory yet")
    ok, msg = bc.check_trajectory(str(REPO))
    assert ok, msg
