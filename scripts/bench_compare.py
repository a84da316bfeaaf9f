#!/usr/bin/env python
"""Bench-trajectory diff: compare bench records across rounds.

The repo accumulates one ``BENCH_r*.json`` per bench round (the driver's
``{n, cmd, rc, tail, parsed}`` wrapper around ``bench.py``'s single JSON
line). Each round is a point on the project's performance trajectory;
this tool turns the set into one consolidated, diffable artifact and
gates new numbers against it:

- ``--build`` flattens every round's ``parsed`` record into dotted
  numeric paths (``prefix_serving.ttft_p50_ms``), groups them by
  ``device_kind`` (a CPU-mesh harness number must never band against a
  real-chip number), and writes ``BENCH_TRAJECTORY.json`` with per-metric
  tolerance bands anchored on the most recent value.
- ``--record FILE`` compares one fresh bench record (a raw ``bench.py``
  output line or a round wrapper) against the committed bands and prints
  ONE parseable verdict line: ``{"bench_compare": {"ok": ..., "checked":
  N, "regressed": [...], ...}}``. A metric is *regressed* when it moved
  past its band in the bad direction — direction is inferred from the
  metric name (``*_ms``/``wall_*``/``ttft*`` lower-better;
  ``tokens_per_sec``/``*speedup``/``hit_rate`` higher-better; unknown
  names are informational only).
- ``--check`` (the ``scripts/lint.sh`` hook, mirroring the
  ``SANITIZER.json`` runtime-report cross-check) re-derives the
  trajectory from the committed rounds and fails when
  ``BENCH_TRAJECTORY.json`` is stale, then verdicts the newest
  successful round against the bands of the rounds before it.

Stdlib-only on purpose: it must run anywhere the repo checks out,
including inside the tier-1 suite (``tests/test_bench_compare.py``).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

TRAJECTORY = "BENCH_TRAJECTORY.json"
DEFAULT_TOLERANCE = 0.25

# direction inference, checked on the LAST dotted segment, higher-better
# patterns first (so "ttft_p50_speedup" reads as a speedup, not a TTFT)
_HIGHER = ("tokens_per_sec", "throughput", "speedup", "hit_rate",
           "accept_rate", "gain", "gbps", "mfu", "tflops", "value",
           "max_concurrent", "parity", "bandwidth", "goodput")
_LOWER = ("_ms", "wall", "ttft", "tpot", "mttr", "lag", "overhead",
          "dip", "seconds", "preemption", "recompile", "eviction",
          "read_amplification", "conservation")
# flattened subtrees that are snapshots/config, not trajectory metrics
_SKIP_KEYS = ("monitor", "tail", "cmd", "model", "trie", "kv_stats",
              "compile_counts", "critical_path", "health", "outcomes",
              "replica_states", "weight_versions", "detail")


def direction(path: str) -> str | None:
    """'higher' / 'lower' / None (informational) for a dotted path."""
    leaf = path.rsplit(".", 1)[-1]
    for pat in _HIGHER:
        if pat in leaf:
            return "higher"
    for pat in _LOWER:
        if pat in leaf:
            return "lower"
    if leaf.endswith("_s"):
        return "lower"
    return None


def flatten(node, prefix: str = "", out: dict | None = None) -> dict:
    """Numeric leaves of a nested record as ``{dotted.path: value}``
    (bools, strings, lists, and the ``_SKIP_KEYS`` subtrees are
    dropped — bands only make sense over scalars)."""
    if out is None:
        out = {}
    if isinstance(node, dict):
        for k, v in node.items():
            if k in _SKIP_KEYS:
                continue
            flatten(v, f"{prefix}.{k}" if prefix else str(k), out)
    elif isinstance(node, (int, float)) and not isinstance(node, bool):
        out[prefix] = float(node)
    return out


def load_rounds(repo: str) -> list[dict]:
    """Every ``BENCH_r*.json`` in round order, normalized to
    ``{n, file, rc, device_kind, metrics}`` (metrics None for rounds
    whose bench run produced no parseable record)."""
    rounds = []
    for path in sorted(glob.glob(os.path.join(repo, "BENCH_r*.json"))):
        if os.path.basename(path) == TRAJECTORY:
            continue
        with open(path) as f:
            raw = json.load(f)
        parsed = raw.get("parsed")
        ok = isinstance(parsed, dict) and parsed.get("value") is not None
        rounds.append({
            "n": raw.get("n"),
            "file": os.path.basename(path),
            "rc": raw.get("rc"),
            "device_kind": (parsed or {}).get("device_kind"),
            "metrics": flatten(parsed) if ok else None,
        })
    return rounds


def build_trajectory(repo: str,
                     tolerance: float = DEFAULT_TOLERANCE) -> dict:
    """The consolidated artifact: per-device-kind bands over every
    successful round, anchored on the most recent value (``last``) with
    the observed min/max alongside — so the band carries both the
    current expectation and the historical envelope."""
    rounds = load_rounds(repo)
    bands: dict[str, dict] = {}
    for r in rounds:
        if r["metrics"] is None:
            continue
        kind = str(r["device_kind"])
        group = bands.setdefault(kind, {})
        for path, v in r["metrics"].items():
            entry = group.setdefault(
                path, {"last": v, "min": v, "max": v, "n": 0,
                       "direction": direction(path)})
            entry["last"] = v
            entry["min"] = min(entry["min"], v)
            entry["max"] = max(entry["max"], v)
            entry["n"] += 1
    return {
        "tolerance": tolerance,
        "rounds": [{k: r[k] for k in ("n", "file", "rc", "device_kind")}
                   for r in rounds],
        "bands": bands,
    }


def compare(metrics: dict, device_kind, trajectory: dict,
            tolerance: float | None = None) -> dict:
    """One record's flattened metrics vs the trajectory's bands for its
    device kind. Regression = worse than ``last * (1 +/- tolerance)``
    in the metric's bad direction; unknown-direction metrics are
    informational. Returns the verdict dict (``ok`` is False only on
    regressions)."""
    tol = (trajectory.get("tolerance", DEFAULT_TOLERANCE)
           if tolerance is None else tolerance)
    group = trajectory.get("bands", {}).get(str(device_kind), {})
    regressed, improved, new, info = [], [], [], 0
    checked = 0
    for path, v in sorted(metrics.items()):
        band = group.get(path)
        if band is None:
            new.append(path)
            continue
        d = band.get("direction")
        if d is None:
            info += 1
            continue
        checked += 1
        base = band["last"]
        scale = max(abs(base), 1e-9)
        if d == "higher" and v < base - tol * scale:
            regressed.append({"metric": path, "value": v, "baseline": base})
        elif d == "lower" and v > base + tol * scale:
            regressed.append({"metric": path, "value": v, "baseline": base})
        elif ((d == "higher" and v > base + tol * scale)
              or (d == "lower" and v < base - tol * scale)):
            improved.append({"metric": path, "value": v, "baseline": base})
    missing = sorted(set(group) - set(metrics))
    return {
        "ok": not regressed,
        "device_kind": device_kind,
        "tolerance": tol,
        "checked": checked,
        "informational": info,
        "regressed": regressed,
        "improved": improved,
        "new": sorted(new),
        "missing": missing,
    }


def _load_record(path: str) -> dict:
    """A fresh record: either bench.py's own JSON line or a round
    wrapper holding it under ``parsed``."""
    with open(path) as f:
        raw = json.load(f)
    return raw.get("parsed") if isinstance(raw.get("parsed"), dict) \
        else raw


def check_trajectory(repo: str) -> tuple[bool, str]:
    """The lint-hook pass: committed trajectory must match a rebuild
    from the committed rounds, and the newest successful round must sit
    inside the bands derived from the rounds BEFORE it."""
    tpath = os.path.join(repo, TRAJECTORY)
    if not os.path.exists(tpath):
        return False, f"{TRAJECTORY} missing: run bench_compare.py --build"
    with open(tpath) as f:
        committed = json.load(f)
    rebuilt = build_trajectory(repo, committed.get("tolerance",
                                                   DEFAULT_TOLERANCE))
    if rebuilt != committed:
        return False, (f"{TRAJECTORY} is stale vs BENCH_r*.json: re-run "
                       "bench_compare.py --build and commit the result")
    successes = [r for r in load_rounds(repo) if r["metrics"] is not None]
    if len(successes) < 2:
        return True, ("trajectory consistent; "
                      f"{len(successes)} successful round(s) — nothing "
                      "to band against")
    latest = successes[-1]
    prior = build_trajectory_from(successes[:-1],
                                  committed.get("tolerance",
                                                DEFAULT_TOLERANCE))
    verdict = compare(latest["metrics"], latest["device_kind"], prior)
    print(json.dumps({"bench_compare": verdict}))
    if not verdict["ok"]:
        return False, (f"round {latest['file']} regressed "
                       f"{len(verdict['regressed'])} metric(s)")
    return True, (f"round {latest['file']}: {verdict['checked']} metrics "
                  "inside tolerance bands")


def build_trajectory_from(rounds: list[dict], tolerance: float) -> dict:
    """Bands over an explicit round list (the --check prior-rounds
    view)."""
    bands: dict[str, dict] = {}
    for r in rounds:
        if r["metrics"] is None:
            continue
        group = bands.setdefault(str(r["device_kind"]), {})
        for path, v in r["metrics"].items():
            entry = group.setdefault(
                path, {"last": v, "min": v, "max": v, "n": 0,
                       "direction": direction(path)})
            entry["last"] = v
            entry["min"] = min(entry["min"], v)
            entry["max"] = max(entry["max"], v)
            entry["n"] += 1
    return {"tolerance": tolerance, "rounds": [], "bands": bands}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--repo", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        help="repo root holding BENCH_r*.json (default: this script's)")
    ap.add_argument("--build", action="store_true",
                    help=f"rebuild {TRAJECTORY} from BENCH_r*.json")
    ap.add_argument("--check", action="store_true",
                    help="verify the committed trajectory is current and "
                         "the newest round sits in the prior bands "
                         "(the scripts/lint.sh hook)")
    ap.add_argument("--record", metavar="FILE",
                    help="compare one fresh bench record JSON against "
                         "the committed bands; prints a verdict line")
    ap.add_argument("--tolerance", type=float, default=None,
                    help="relative band width (default: the "
                         f"trajectory's, else {DEFAULT_TOLERANCE})")
    args = ap.parse_args(argv)
    if not (args.build or args.check or args.record):
        ap.error("pick one of --build / --check / --record FILE")
    if args.build:
        traj = build_trajectory(args.repo,
                                args.tolerance or DEFAULT_TOLERANCE)
        out = os.path.join(args.repo, TRAJECTORY)
        with open(out, "w") as f:
            json.dump(traj, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"wrote {out}: {len(traj['rounds'])} rounds, "
              f"{sum(len(g) for g in traj['bands'].values())} banded "
              f"metrics over {len(traj['bands'])} device kind(s)")
    if args.record:
        tpath = os.path.join(args.repo, TRAJECTORY)
        with open(tpath) as f:
            trajectory = json.load(f)
        rec = _load_record(args.record)
        verdict = compare(flatten(rec), rec.get("device_kind"),
                          trajectory, tolerance=args.tolerance)
        print(json.dumps({"bench_compare": verdict}))
        return 0 if verdict["ok"] else 1
    if args.check:
        ok, msg = check_trajectory(args.repo)
        print(f"bench_compare --check: {msg}")
        return 0 if ok else 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
