"""From the profiler's ``.xplane.pb`` to numbers: busy and idle time of the
device, device time under each host span, time per group of operations, and
the breakdown (top device operations; longest idle gaps by what the host was
doing). Read with ``xplane.py`` (``google.protobuf`` alone), which also shows
the statistics of an event's metadata: the operation's category and the name
stack it was traced under.

``python3 benchmarks/harness/trace.py --selfcheck`` reduces the small recorded
trace beside this file and compares with values counted by hand
(``testdata/recorded.expected.json``); ``--dump FILE`` lists what a trace
holds.

The trace: one plane per chip (``/device:TPU:n``) whose ``XLA Ops`` line holds
every operation that ran there, nested where one contains others (a loop and
its body); one host plane (``/host:CPU``) with a line per thread, on which
``jax.profiler.TraceAnnotation`` spans (the program's ``chainermn.*``) lie.
Both are on one clock, nanoseconds from the start of the session. The harness
writes one span of its own, ``bench.mark``, and reads the host's
``perf_counter`` inside it: that ties the two clocks together.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import shutil
import sys
import tempfile
import time

MARK = "bench.mark"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
BLOCK = re.compile(r"(?:^|/)(block_\d+)(?:/|$)")
SHORT_GAP = 20e-6      # idle gaps under this are summed, not charged to a span


class Session:
    """One traced stretch of a run. Python's own call tracer is off: it
    multiplies the host's work, and the spans that matter are TraceMe's."""

    def __init__(self) -> None:
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.t_mark = None
        self.t_stop = None

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def mark(self) -> None:
        import jax

        with jax.profiler.TraceAnnotation(MARK):
            self.t_mark = time.perf_counter()

    def stop(self) -> None:
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()

    def file(self) -> str:
        found = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not found:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        return found[0]

    def reduce(self) -> "Reduced":
        try:
            return Reduced(self.file(), self.t_mark, self.t_stop)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def op_path(stats: dict) -> str:
    """The name stack an operation was traced under (``jax.named_scope`` and
    flax module names), as the compiler kept it in the op's metadata
    (``tf_op``), e.g. ``jit(body)/chainermn.decode/TransformerLM/block_3/
    pallas_call:``."""
    v = stats.get("tf_op") or stats.get("hlo_op") or ""
    return v.rstrip(":") if isinstance(v, str) else ""


def op_category(name: str, stats: dict) -> str:
    v = stats.get("hlo_category")
    if isinstance(v, str) and v:
        return v
    return re.sub(r"[.\d]+$", "", name.split(" = ")[0].lstrip("%"))


def merged(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_times(events: list) -> list:
    """``events`` = ``[(start, end, ...)]`` of one line. Returns each event's
    own time: its length less that of the events nested directly in it."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -(events[i][1])))
    own = [events[i][1] - events[i][0] for i in range(len(events))]
    stack = []
    for i in order:
        s, e = events[i][0], events[i][1]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= (e - s)
        stack.append(i)
    return own


class Reduced:
    """A trace, reduced. Times are seconds on the trace's clock unless a
    method says otherwise; ``to_trace(t)`` maps a ``perf_counter`` reading."""

    def __init__(self, path: str, t_mark=None, t_stop=None) -> None:
        from harness import xplane

        data = xplane.load(path)
        self.devices = {}        # ordinal -> [(start, end, name, path, cat)]
        self.host = []           # (start, end, name, thread)
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                ops = []
                for line in plane.lines:
                    if line.name != OPS_LINE:
                        continue
                    for s, e, name, st in xplane.events(plane, line):
                        ops.append((s, e, name.split(" = ")[0].lstrip("%"),
                                    op_path(st), op_category(name, st)))
                self.devices[int(m.group(1))] = ops
            elif plane.name == "/host:CPU":
                cpu_ops = []
                for line in plane.lines:
                    for s, e, name, st in xplane.events(plane, line):
                        if "hlo_op" in st:
                            cpu_ops.append((s, e, name, op_path(st),
                                            op_category(name, st)))
                        else:
                            self.host.append((s, e, name, line.name))
        if not self.devices:
            # a CPU rehearsal: XLA:CPU writes its operations on host lines,
            # marked by the ``hlo_op`` statistic
            self.devices[0] = cpu_ops
        marks = [h for h in self.host if h[2] == MARK]
        if marks and t_mark is not None:
            self.offset = marks[0][0] - t_mark      # trace = perf + offset
            self.begin = marks[0][0]
            self.end = t_stop + self.offset
        else:
            every = [o for ops in self.devices.values() for o in ops]
            self.offset = 0.0
            self.begin = min((o[0] for o in every), default=0.0)
            self.end = max((o[1] for o in every), default=0.0)
        self.window_s = self.end - self.begin
        for k, ops in self.devices.items():
            self.devices[k] = sorted(
                (max(o[0], self.begin), min(o[1], self.end)) + o[2:]
                for o in ops if o[1] > self.begin and o[0] < self.end)
        self._own = {k: self_times(ops) for k, ops in self.devices.items()}
        self._busy = {k: merged([(o[0], o[1]) for o in ops])
                      for k, ops in self.devices.items()}

    # -- clocks ----------------------------------------------------------- #

    def to_trace(self, t_perf: float) -> float:
        return t_perf + self.offset

    def to_perf(self, t_trace: float) -> float:
        return t_trace - self.offset

    # -- device ----------------------------------------------------------- #

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        per = [sum(e - s for s, e in iv) for iv in self._busy.values()]
        return sum(per) / len(per) if per else 0.0

    def busy_between(self, start: float, end: float, device: int = 0) -> float:
        """Device-busy seconds inside ``[start, end)`` of the trace clock."""
        iv = self._busy.get(device, [])
        i = bisect.bisect_left(iv, [start, start])
        if i > 0 and iv[i - 1][1] > start:
            i -= 1
        total = 0.0
        while i < len(iv) and iv[i][0] < end:
            total += max(0.0, min(iv[i][1], end) - max(iv[i][0], start))
            i += 1
        return total

    def op_seconds(self, pick, device=None) -> float:
        """Own time of the operations ``pick(name, path, category)`` accepts,
        averaged over the chips (or on one)."""
        keys = list(self.devices) if device is None else [device]
        total = 0.0
        for k in keys:
            for o, own in zip(self.devices[k], self._own[k]):
                if pick(o[2], o[3], o[4]):
                    total += own
        return total / len(keys) if keys else 0.0

    def ops_between(self, pick, device: int = 0) -> list:
        """``(start, end)`` of the picked operations on one chip."""
        return [(o[0], o[1]) for o in self.devices.get(device, [])
                if pick(o[2], o[3], o[4])]

    # -- host ------------------------------------------------------------- #

    def spans(self, name: str) -> list:
        """``(start, end)`` of the host spans of that name inside the window,
        in order, on the trace's clock."""
        return sorted((s, e) for s, e, n, _ in self.host
                      if n == name and s >= self.begin and e <= self.end)

    # -- breakdown -------------------------------------------------------- #

    def group_of(self, name: str, path: str, category: str) -> str:
        """Operations alike in every layer are one group: the last two parts
        of the name stack with the numbers taken out, and the category."""
        where = "/".join(path.split("/")[-2:]) if path else name
        where = re.sub(r"\d+", "N", where)
        return f"{where}:{category}"

    def breakdown(self, top: int = 10) -> dict:
        groups = {}
        for k, ops in self.devices.items():
            for o, own in zip(ops, self._own[k]):
                g = self.group_of(o[2], o[3], o[4])
                groups[g] = groups.get(g, 0.0) + own / len(self.devices)
        device_ops = sorted(groups.items(), key=lambda kv: -kv[1])[:top]
        # idle gaps of the first chip, each charged to the host event that
        # covers most of it (the innermost, where several do)
        busy = self._busy.get(min(self.devices), []) if self.devices else []
        gaps, prev = [], self.begin
        for s, e in busy:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if self.end > prev:
            gaps.append((prev, self.end))
        host = sorted(h for h in self.host if h[2] != MARK)
        starts = [h[0] for h in host]
        long_events = [h for h in host if h[1] - h[0] > 5e-3]
        charged = {}
        for gs, ge in gaps:
            if ge - gs < SHORT_GAP:
                name = f"gaps_under_{int(SHORT_GAP * 1e6)}us"
                charged[name] = charged.get(name, 0.0) + (ge - gs)
                continue
            hi = bisect.bisect_left(starts, ge)
            best, best_len, best_span = None, 0.0, None
            for h in long_events + host[max(0, hi - 400):hi]:
                ov = min(h[1], ge) - max(h[0], gs)
                if ov <= 0:
                    continue
                span = h[1] - h[0]
                if best is None or ov > best_len * 1.0001 or (
                        ov >= best_len * 0.9999 and span < best_span):
                    best, best_len, best_span = h[2], ov, span
            name = best if best else "host:_no_span__asleep_or_waiting_"
            charged[name] = charged.get(name, 0.0) + (ge - gs)
        idle = sorted(charged.items(), key=lambda kv: -kv[1])[:top]
        clean = lambda n: re.sub(r"[^A-Za-z0-9_.:/-]", "_", n)[:80]
        return {"device_ops": [[clean(n), s] for n, s in device_ops],
                "idle_gaps": [[clean(n), s] for n, s in idle]}

    def summary(self) -> dict:
        return {"window_s": self.window_s, "busy_s": self.busy_s,
                "idle_share": 1.0 - self.busy_s / self.window_s
                if self.window_s else None,
                "devices": len(self.devices),
                "ops": sum(len(o) for o in self.devices.values()),
                "host_events": len(self.host),
                "breakdown": self.breakdown()}


def dump(path: str, limit: int = 6) -> None:
    """What a trace holds: planes, lines, counts and a few events of each
    line with all their statistics."""
    from harness import xplane

    for plane in xplane.load(path).planes:
        print(f"PLANE {plane.name!r} lines={len(plane.lines)}")
        for line in plane.lines:
            print(f"  LINE {line.name!r} events={len(line.events)}")
            seen = set()
            for s, e, name, st in xplane.events(plane, line):
                key = re.sub(r"[.\d]+", "", name.split(" = ")[0])
                if key in seen or len(seen) >= limit:
                    continue
                seen.add(key)
                print(f"    {name[:120]!r} start={s:.6f} dur={e - s:.6f} "
                      f"stats={ {k: str(v)[:80] for k, v in st.items()} }")


def selfcheck() -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    rec = os.path.join(here, "testdata", "recorded.xplane.pb")
    with open(os.path.join(here, "testdata", "recorded.expected.json")) as f:
        want = json.load(f)["values"]
    red = Reduced(rec)
    got = {"window_s": red.window_s, "busy_s": red.busy_s,
           "n_ops": sum(len(o) for o in red.devices.values()),
           "spans": {n: len(red.spans(n)) for n in want["spans"]},
           "group_s": {g: red.op_seconds(
               lambda n, p, c, g=g: red.group_of(n, p, c) == g)
               for g in want["group_s"]}}
    bad = []

    def cmp(a, b, where):
        if isinstance(b, dict):
            for k in b:
                cmp(a.get(k), b[k], f"{where}.{k}")
        elif isinstance(b, float):
            if a is None or abs(a - b) > 1e-9 + 1e-6 * abs(b):
                bad.append(f"{where}: got {a!r}, counted by hand {b!r}")
        elif a != b:
            bad.append(f"{where}: got {a!r}, counted by hand {b!r}")

    cmp(got, want, "trace")
    for line in bad:
        print(line)
    print("trace reduction self-check:", "FAILED" if bad else "ok")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if len(sys.argv) >= 3 and sys.argv[1] == "--dump":
        dump(sys.argv[2])
    elif len(sys.argv) >= 3 and sys.argv[1] == "--summary":
        print(json.dumps(Reduced(sys.argv[2]).summary(), indent=1))
    elif "--selfcheck" in sys.argv:
        sys.exit(selfcheck())
    else:
        print(__doc__)
