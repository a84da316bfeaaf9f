"""Arithmetic shared by the metric readers under ``metrics/``. A reader takes
the run's record (what a driver measured: the window, the harness's stamps,
the reduced trace where there is one) and returns a number, or ``None`` where
it finds nothing to read: the harness then leaves the metric out of the line.
"""

from __future__ import annotations

import bisect
import re

from harness import peaks, work
from harness.common import percentile

DECODE_SPAN = "chainermn.serving_decode"
PREFILL_SPAN = "chainermn.serving_prefill"


def stamps_in_window(run: dict) -> list:
    t0, t1 = run["t0"], run["t1"]
    return [s for r in run["requests"] for s in r.stamps if t0 <= s < t1]


def token_gaps_ms(run: dict) -> list:
    """Gaps between consecutive tokens of a request, both inside the window."""
    t0, t1 = run["t0"], run["t1"]
    out = []
    for r in run["requests"]:
        st = r.stamps
        for a, b in zip(st, st[1:]):
            if a >= t0 and b < t1:
                out.append((b - a) * 1e3)
    return out


def gen_late_ms_p99(run: dict):
    t0, t1 = run["t0"], run["t1"]
    late = [(r.sent - r.due) * 1e3 for r in run["requests"]
            if t0 <= r.due < t1]
    return percentile(late, 99) if late else None


def _traced(run: dict):
    tr = run.get("trace")
    return tr if tr is not None and tr.window_s > 0 else None


def idle_share_pct(run: dict):
    tr = _traced(run)
    if tr is None or tr.busy_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def span_device_ms_p50(run: dict, span: str):
    """Median, over the host spans of that name, of the device-busy time
    inside the span (the span ends with the fetch of its program's result)."""
    tr = _traced(run)
    if tr is None:
        return None
    times = [tr.busy_between(s, e) * 1e3 for s, e in tr.spans(span)]
    return percentile(times, 50) if times else None


def _traced_tokens(run: dict) -> tuple:
    """Within the traced stretch: (decoded tokens, sum of their contexts,
    prompt tokens prefilled, sum of the prompts' causal contexts)."""
    tr = run["trace"]
    a, b = tr.to_perf(tr.begin), tr.to_perf(tr.end)
    return tokens_between(run, a, b)


def tokens_between(run: dict, a: float, b: float) -> tuple:
    dec, dec_ctx, pre, pre_ctx = 0, 0.0, 0, 0.0
    for r in run["requests"]:
        p = len(r.prompt)
        for i, s in enumerate(r.stamps):
            if not a <= s < b:
                continue
            if i == 0:                    # the prefill produced this token
                pre += p
                pre_ctx += p * (p + 1) / 2.0
            else:                         # decode step: context p + i
                dec += 1
                dec_ctx += p + i
    return dec, dec_ctx, pre, pre_ctx


def batch_occupancy_pct(run: dict):
    """Live slots per decode step of the traced stretch. The tokens of step
    ``i`` are stamped after its span ends and before the next begins (first
    tokens, which a prefill produced, are not counted), so the last span of
    the stretch, whose tokens may fall outside it, is left out."""
    tr = _traced(run)
    if tr is None:
        return None
    spans = tr.spans(DECODE_SPAN)
    if len(spans) < 2:
        return None
    ends = [tr.to_perf(e) for _, e in spans[:-1]]
    last = tr.to_perf(spans[-1][0])
    delivered = 0
    for r in run["requests"]:
        for s in r.stamps[1:]:
            if ends[0] <= s < last:
                delivered += 1
    return 100.0 * delivered / ((len(spans) - 1) * run["n_slots"])


def kv_pool_live_share_pct(run: dict):
    """Tokens live in the block store, averaged over the window, over the
    tokens the store can hold. A request holds its prompt and the tokens it
    has been given so far from its first stamp until its last (its slot is
    then free) or, where the close cut it short, until the close."""
    t0, t1 = run["t0"], run["t1"]
    held = 0.0
    for r in run["requests"]:
        st, p = r.stamps, len(r.prompt)
        if not st or st[0] >= t1:
            continue
        ends = st[1:] + ([st[-1]] if len(st) >= r.max_new else [t1])
        for i, (a, b) in enumerate(zip(st, ends)):
            held += (p + i + 1) * max(0.0, min(b, t1) - max(a, t0))
    if held <= 0:
        return None
    return 100.0 * held / ((t1 - t0) * run["kv_pool_tokens"])


def serve_step_mfu_pct(run: dict):
    """Model FLOPs of every token processed in the window (prompts prefilled
    and tokens decoded, attention over the contexts really held) over the
    window's seconds and the chip's peak."""
    dec, dec_ctx, pre, pre_ctx = tokens_between(run, run["t0"], run["t1"])
    if dec + pre == 0:
        return None
    flops = work.lm_forward_flops(run["config"], dec + pre, dec_ctx + pre_ctx)
    peak = peaks.peak(run["device"]["kind"])["flops_per_s"]
    return 100.0 * flops / (run["seconds"] * peak)


def in_block_attention(name: str, path: str, category: str) -> bool:
    """An operation that implements attention in a transformer block: traced
    directly under ``block_N`` and not inside one of its dense or norm
    sub-modules, and not one of the block's own element-wise leftovers (the
    residual additions, the activation). Found by where it was traced, not by
    a kernel's name, so a kernel that replaces today's is held to the same
    work."""
    m = re.search(r"(?:^|/)block_\d+/(.*)$", path)
    if not m:
        return False
    rest = m.group(1)
    if re.match(r"(qkv|proj|Dense_\d+|LayerNorm_\d+|mlp|attn/(qkv|proj))/",
                rest):
        return False
    leaf = rest.rsplit("/", 1)[-1]
    return leaf not in ("add", "tanh", "gelu", "mul", "integer_pow")


def paged_decode_roofline_pct(run: dict):
    tr = _traced(run)
    if tr is None:
        return None
    # decode attention runs only under the decode spans
    spans = tr.spans(DECODE_SPAN)
    ops = tr.ops_between(in_block_attention)
    if not spans or not ops:
        return None
    starts = [s for s, _ in spans]
    seconds = 0.0
    for s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < spans[i][1]:
            seconds += e - s
    dec, dec_ctx, _, _ = _traced_tokens(run)
    if seconds <= 0 or dec == 0:
        return None
    need = work.paged_decode_attention(
        run["config"], run["traffic"]["engine"], dec_ctx, dec)
    least = work.roofline_seconds(need, peaks.peak(run["device"]["kind"]))
    return 100.0 * least / seconds


def train_step_mfu_pct(run: dict):
    if not run.get("steps"):
        return None
    peak = peaks.peak(run["device"]["kind"])["flops_per_s"]
    return 100.0 * run["flops_per_step"] * run["steps"] / (
        run["seconds"] * run["cell"]["chips"] * peak)


def flash_roofline_pct(run: dict):
    tr = _traced(run)
    if tr is None or not run.get("traced_steps"):
        return None
    seconds = tr.op_seconds(in_block_attention)
    if seconds <= 0:
        return None
    need = run["attention_work_per_step"]
    least = work.roofline_seconds(
        {k: v * run["traced_steps"] for k, v in need.items()},
        peaks.peak(run["device"]["kind"]))
    return 100.0 * least / seconds
