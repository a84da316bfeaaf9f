"""Observability: step timing, per-step collective-traffic stats, profiler
trace helper, and a hang watchdog.

The reference ships NO profiling of its own (SURVEY.md S5: users reach for
Chainer hooks + nvprof; the paper profiles externally) and no hang
detection (a lost collective blocks forever in NCCL/MPI). The TPU rebuild
owes both: XLA gives tracing nearly free (``jax.profiler``), compiled
programs make comm traffic *statically knowable* (read the collectives out
of the lowered HLO instead of instrumenting a byte-mover), and XLA
collectives hang exactly like NCCL ones, so a watchdog turns silent stalls
into actionable failures (the same fail-fast stance as
``global_except_hook``, SURVEY.md S3.5).
"""

from __future__ import annotations

import contextlib
import re
import sys
import threading
import time
from typing import Any, Callable, Optional

import numpy as np

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast",
)

# `%name = TYPE op-name(` — TYPE is `f32[8,128]{...}` or a (tuple, of,
# them). The type is captured LAZILY up to the first lowercase
# word-followed-by-"(" — the op name — because real TPU layouts embed
# parens inside the braces (`{1,0:T(8,128)(2,1)S(1)}`), which a greedy
# "(...)" alternation cannot survive (that bug silently dropped every
# collective-permute-start from round-3-era counts).
_INSTR_RE = re.compile(r"=\s*(.*?)\s*([a-z][a-z0-9-]*(?:\.[0-9]+)?)\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_list(type_str: str) -> list[tuple[int, bool]]:
    """[(bytes, is_control), ...] for every array shape in a type string
    (layout annotations are ignored). Control words — the u32[] scalars TPU
    async-starts append to their tuples — are flagged BY DTYPE AND RANK so
    they can be filtered from payload math; a genuinely scalar payload of
    any other dtype (an f32[] loss psum) stays a payload."""
    out = []
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in _DTYPE_BYTES:
            continue  # token types etc.
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append((n * _DTYPE_BYTES[dtype], dtype == "u32" and dims == ""))
    return out


def _type_bytes(type_str: str) -> int:
    return sum(b for b, _ in _shape_list(type_str))


def parse_hlo_collectives(hlo: str) -> dict[str, Any]:
    """Count collectives + their output bytes in HLO text.

    Post-optimization TPU/GPU HLO rewrites collectives into async
    ``<op>-start`` / ``<op>-done`` pairs: the ``-start`` carries the payload
    type and is counted under the base op name (TPU starts append u32[]
    control scalars to the tuple — filtered out of the payload math);
    ``-done`` is skipped so pairs aren't double-counted. Collectives inside
    a ``while`` body (e.g. a ring's per-step ppermute) count ONCE, not once
    per iteration — this reports the program's collective *structure*; wire
    volume per step multiplies by the trip count.
    """
    stats: dict[str, Any] = {}
    total = 0
    for m in _INSTR_RE.finditer(hlo):
        type_str, op = m.group(1), m.group(2)
        op = op.split(".")[0]  # strip .N instance suffixes
        if op.endswith("-done"):
            continue
        is_start = op.endswith("-start")
        base = op[: -len("-start")] if is_start else op
        if base not in _COLLECTIVES:
            continue
        if is_start and type_str.startswith("("):
            els = [b for b, control in _shape_list(type_str) if not control]
            if not els:
                els = [b for b, _ in _shape_list(type_str)]
            if base == "all-reduce":
                # all-reduce-start's tuple members are all RESULTS (XLA's
                # all-reduce combiner emits variadic ops): count every one.
                nbytes = sum(els)
            elif len(els) % 2 == 0:
                # other async starts return (operands..., results...) pairs —
                # count the result half, matching the op's sync form (sum
                # would double-count; max picks the operand for
                # reduce-scatter).
                nbytes = sum(els[len(els) // 2 :])
            else:
                nbytes = max(els, default=0)
        else:
            nbytes = _type_bytes(type_str)
        entry = stats.setdefault(base, {"count": 0, "bytes": 0})
        entry["count"] += 1
        entry["bytes"] += nbytes
        total += nbytes
    stats["total_bytes"] = total
    return stats


# Memoized lowered-HLO text per (jitted fn, abstract arg shapes): the AOT
# ``lower().compile()`` below does not share the jit executable cache, so
# without this every collective_stats call paid one full extra XLA compile
# of a function the jit cache had already built. Keyed by id() but guarded by a weakref identity check so
# a recycled id can never serve another function's HLO.
_HLO_MEMO_MAX = 64
_hlo_memo: "dict[tuple, tuple]" = {}
_hlo_memo_info = {"hits": 0, "misses": 0}


def _abstract_sig(args, kwargs):
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    sig = []
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            sig.append((tuple(leaf.shape), str(leaf.dtype)))
        else:
            sig.append(repr(leaf))
    return (treedef, tuple(sig))


def _lowered_hlo(jitted, args, kwargs) -> str:
    import weakref

    try:
        ref = weakref.ref(jitted)
    except TypeError:
        return jitted.lower(*args, **kwargs).compile().as_text()
    key = (id(jitted), _abstract_sig(args, kwargs))
    hit = _hlo_memo.get(key)
    if hit is not None and hit[0]() is jitted:
        _hlo_memo_info["hits"] += 1
        return hit[1]
    _hlo_memo_info["misses"] += 1
    hlo = jitted.lower(*args, **kwargs).compile().as_text()
    if len(_hlo_memo) >= _HLO_MEMO_MAX:  # bounded: drop the oldest entry
        _hlo_memo.pop(next(iter(_hlo_memo)))
    _hlo_memo[key] = (ref, hlo)
    return hlo


def collective_stats(fn: Callable, *args, **kwargs) -> dict[str, Any]:
    """Statically analyze one step's collective traffic from compiled HLO.

    ``fn`` is a jitted (or jittable) function; ``args`` example inputs.
    Returns ``{op: {"count": n, "bytes": output_bytes}, ...,
    "total_bytes": N}`` — output-shape bytes per collective, the standard
    proxy for wire traffic (all-gather output == gathered bytes, all-reduce
    output ~= ring traffic x 2(n-1)/n).

    This replaces instrumenting a hand-written byte-mover (the reference
    would count what it memcpy'd): under XLA the program IS the ground
    truth. The AOT ``lower().compile()`` does not share the jit executable
    cache, so the lowered HLO text is memoized per (jitted fn, abstract
    shapes): repeated calls pay the extra XLA compile once, not every
    time.
    """
    import jax

    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    return parse_hlo_collectives(_lowered_hlo(jitted, args, kwargs))


def latency_report(samples, prefix: str) -> dict[str, float]:
    """``{prefix}_mean_s`` / ``{prefix}_p50_s`` / ``{prefix}_p99_s`` from a
    list of second-valued samples — the one percentile convention every
    latency surface (``StepTimer`` steps, serving TTFT/TPOT) reports in, so
    records from training and serving benchmarks stay field-compatible.
    Empty input returns ``{}`` (no samples is not 0 latency)."""
    if not len(samples):
        return {}
    t = np.asarray(samples, dtype=np.float64)
    return {
        f"{prefix}_mean_s": float(t.mean()),
        f"{prefix}_p50_s": float(np.percentile(t, 50)),
        f"{prefix}_p99_s": float(np.percentile(t, 99)),
    }


class StepTimer:
    """Wall-clock step statistics with warmup exclusion.

    Use as a context manager around each step (or call ``tick()`` once per
    step); ``report()`` returns mean/p50/p99 step time and items/sec. The
    per-step comm-bytes x step-time pairing (SURVEY.md S5) comes from
    combining this with :func:`collective_stats`.
    """

    def __init__(self, warmup: int = 2, items_per_step: int = 0) -> None:
        self._warmup = warmup
        self._items = items_per_step
        self._times: list[float] = []
        self._seen = 0
        self._t0: Optional[float] = None
        self._last: Optional[float] = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._record(time.perf_counter() - self._t0)

    def tick(self) -> None:
        """Alternative to the context manager: call once per completed step
        (the first call only arms the clock)."""
        now = time.perf_counter()
        if self._last is not None:
            self._record(now - self._last)
        self._last = now

    def _record(self, dt: float) -> None:
        self._seen += 1
        if self._seen > self._warmup:
            self._times.append(dt)

    @property
    def steps(self) -> int:
        return len(self._times)

    def report(self) -> dict[str, float]:
        if not self._times:
            return {"steps": 0}
        out = {"steps": len(self._times)}
        out.update(latency_report(self._times, "step_time"))
        if self._items:
            out["items_per_sec"] = self._items / out["step_time_mean_s"]
        return out


@contextlib.contextmanager
def trace(log_dir: str):
    """``jax.profiler`` trace around a code block; view in XProf/Perfetto.
    (The reference points users at nvprof; this is the TPU equivalent.)"""
    import jax

    with jax.profiler.trace(log_dir):
        yield


class Watchdog:
    """Deadlock watchdog: a hung step (lost collective peer, wedged host
    callback) dumps every thread's stack and — by default — aborts the
    process so the launcher can restart it, instead of hanging silently
    forever the way a lost NCCL/XLA collective does.

    Use around each step::

        dog = Watchdog(timeout=300)
        with dog.step():
            train_step(...)

    ``on_timeout='warn'`` only reports — re-armed each period, so a
    multi-period hang keeps reporting instead of going quiet after one.
    """

    def __init__(self, timeout: float, on_timeout: str = "abort",
                 _sink=None) -> None:
        if on_timeout not in ("abort", "warn"):
            raise ValueError(f"on_timeout must be abort|warn, got {on_timeout!r}")
        self._timeout = timeout
        self._mode = on_timeout
        self._sink = _sink or sys.stderr
        self._fired = threading.Event()
        self._timer: Optional[threading.Timer] = None
        # Generation counter guards the warn-mode re-arm against racing a
        # step() exit: each step entry/exit bumps the generation, and a timer
        # carrying a stale generation discards itself instead of re-arming a
        # watchdog for a step that already finished.
        self._lock = threading.Lock()
        self._gen = 0
        self._armed = False
        self._ctx: dict = {}

    def _fire(self, where: str, gen: int) -> None:
        with self._lock:
            if gen != self._gen or not self._armed:
                return  # the watched step finished; stale timer, stand down
            ctx = dict(self._ctx)
        self._fired.set()
        import faulthandler

        who = (" " + " ".join(f"{k}={v}" for k, v in ctx.items())
               if ctx else "")
        print(
            f"chainermn_tpu.Watchdog: step exceeded {self._timeout}s "
            f"({where}{who}) — a peer likely died inside a collective. "
            "Thread stacks follow.",
            file=self._sink, flush=True,
        )
        try:
            # faulthandler needs a real fd; test sinks (StringIO) don't have
            # one, so fall back to a pure-Python dump in faulthandler's
            # format ("Thread 0x... (most recent call first):").
            self._sink.fileno()
            faulthandler.dump_traceback(file=self._sink)
        except Exception:
            try:
                import traceback

                current = threading.get_ident()
                for tid, frame in sys._current_frames().items():
                    tag = "Current thread" if tid == current else "Thread"
                    print(f"{tag} {tid:#x} (most recent call first):",
                          file=self._sink)
                    for line in reversed(traceback.format_stack(frame)):
                        self._sink.write(line)
                self._sink.flush()
            except Exception:
                pass
        # Flight recorder: what the system was DOING when it wedged — the
        # last N structured events (slot admits/retires, steps, compiles)
        # plus per-device memory stats, not just where threads are parked.
        # once="failure": one dump per failure episode per sink — a warn-
        # mode re-fire or the excepthook that follows an abort re-prints
        # thread stacks but not a duplicate flight record.
        try:
            from chainermn_tpu.monitor import emit, get_event_log

            # ctx carries the caller's request/trace identity (the
            # serving scheduler labels every watched device call), so the
            # fire event joins against exported traces
            emit("watchdog_fire", where=where, timeout_s=self._timeout,
                 mode=self._mode, **ctx)
            get_event_log().dump(file=self._sink, once="failure")
        except Exception:
            pass
        if self._mode == "abort":
            import os

            os._exit(43)  # mirror global_except_hook: die loudly, not hang
        with self._lock:  # warn mode: re-arm so long hangs keep reporting
            if self._armed and gen == self._gen:
                self._start_timer_locked(where)

    def _start_timer_locked(self, label: str) -> None:
        self._timer = threading.Timer(
            self._timeout, self._fire, args=(label, self._gen)
        )
        self._timer.daemon = True
        self._timer.start()

    @property
    def fired(self) -> bool:
        """Whether any watched step has ever timed out (for tests/metrics)."""
        return self._fired.is_set()

    @contextlib.contextmanager
    def step(self, label: str = "train step", **context):
        """Watch one step. ``context`` (request ids, trace ids — whatever
        identifies the work) rides into the ``watchdog_arm``/
        ``watchdog_fire`` events and the fire banner, so a hang dump
        names the victims instead of just the call site."""
        with self._lock:
            self._gen += 1
            self._armed = True
            self._ctx = context
            self._start_timer_locked(label)
        try:  # arm event: correlates hangs with the surrounding activity
            from chainermn_tpu.monitor import emit

            emit("watchdog_arm", label=label, timeout_s=self._timeout,
                 **context)
        except Exception:
            pass
        try:
            yield
        finally:
            with self._lock:
                self._gen += 1
                self._armed = False
                self._ctx = {}
                if self._timer is not None:
                    self._timer.cancel()
