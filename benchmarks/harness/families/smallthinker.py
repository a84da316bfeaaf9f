"""``chainermn_tpu.models.SmallThinkerLM`` from a configuration that keeps the
published ``config.json`` key names, what its serving step costs by the
mathematics (FLOPs of the active parameters and of the visible context, bytes
of the experts hit and of the K/V rows visible per layer kind), and which
traced device operations are its attention, its expert products and the rest
of its mixture layer. The metric files ``metrics/*.shortlong.py`` read a
run's record through the functions at the end.
"""

from __future__ import annotations

import bisect
import re

from harness import families, peaks, readers, work


# --------------------------------------------------------------------------- #
# the model                                                                    #
# --------------------------------------------------------------------------- #

def layers_kept(config: dict) -> tuple:
    """``(window flags, rope flags)`` of the layers kept: the first
    ``num_hidden_layers`` entries of the published layouts."""
    n = config["num_hidden_layers"]
    return (tuple(config["sliding_window_layout"][:n]),
            tuple(config["rope_layout"][:n]))


def build_model(config: dict, **kw):
    from chainermn_tpu.models import SmallThinkerLM

    window_layers, rope_layers = layers_kept(config)
    return SmallThinkerLM(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], n_layers=config["num_hidden_layers"],
        d_ff=config["moe_ffn_hidden_size"],
        n_experts=config["moe_num_primary_experts"],
        top_k=config["moe_num_active_primary_experts"],
        window=config["sliding_window_size"], window_layers=window_layers,
        rope_layers=rope_layers, rope_theta=float(config["rope_theta"]),
        rms_norm_eps=config["rms_norm_eps"],
        max_len=config["max_position_embeddings"],
        compute_dtype=families.dtype(config["compute_dtype"]), **kw)


class _AsKernel:
    """Marks a leaf of the shape tree that ``harness/weights.py`` is to draw
    by its rule for kernels (normal, 1/sqrt of the product of all axes but
    the last) and not at its fallback of 0.02: the leaf's path gains a last
    key ``kernel``, and the tree that comes back holds the bare array where
    the mark was, as the model's own tree does."""

    def __init__(self, leaf) -> None:
        self.leaf = leaf


def _register_mark() -> None:
    import jax

    jax.tree_util.register_pytree_with_keys(
        _AsKernel,
        lambda m: (((jax.tree_util.DictKey("kernel"), m.leaf),), None),
        lambda _, children: children[0])


_register_mark()


def init_shapes(config: dict, model):
    """The model's own tree of shapes. The experts' down projections
    ``[experts, d_ff, d_model]`` are marked as a kernel, which draws them at
    1/sqrt(64 x 768) = 0.0045 and not at 0.02: the configuration's
    ``assumed`` says why (a choice of this benchmark, not the program's)."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    for name, block in shapes.items():
        if name.startswith("block_"):
            block["moe"]["w_down"] = _AsKernel(block["moe"]["w_down"])
    return {"params": shapes}


# --------------------------------------------------------------------------- #
# what the mathematics asks for                                                #
# --------------------------------------------------------------------------- #

def _attn_params(config: dict) -> int:
    d, dh = config["hidden_size"], config["head_dim"]
    h, hk = config["num_attention_heads"], config["num_key_value_heads"]
    return d * h * dh + 2 * d * hk * dh + h * dh * d


def _expert_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_ffn_hidden_size"]


def layer_flops_per_token(config: dict) -> float:
    """Forward FLOPs of one token through the weights it meets in a layer:
    attention projections, the router, ``top_k`` experts. 2 per weight."""
    routed = config["moe_num_active_primary_experts"] * _expert_params(config)
    router = config["hidden_size"] * config["moe_num_primary_experts"]
    return 2.0 * (_attn_params(config) + router + routed)


def head_flops(config: dict) -> float:
    return 2.0 * config["hidden_size"] * config["vocab_size"]


def visible(config: dict, context: float, windowed: bool) -> float:
    """Keys a query with ``context`` positions up to its own sees."""
    return min(context, config["sliding_window_size"]) if windowed \
        else context


def visible_sum_prompt(config: dict, p: int, windowed: bool) -> float:
    """Visible (query, key) pairs of a causal prompt of ``p`` tokens."""
    w = config["sliding_window_size"]
    if not windowed or p <= w:
        return p * (p + 1) / 2.0
    return w * (w + 1) / 2.0 + (p - w) * float(w)


def kv_row_bytes(config: dict, engine: dict) -> float:
    """One token's K and V rows in one layer, in the store's type."""
    hk, dh = config["num_key_value_heads"], config["head_dim"]
    if engine.get("kv_quant") == "int8":
        return 2.0 * hk * (dh + 4)       # int8 rows, a float32 scale a head
    return 2.0 * hk * dh * 2


def tokens_by_kind(run: dict, a: float, b: float) -> dict:
    """From the harness's stamps between ``a`` and ``b``: tokens decoded and
    prompt tokens prefilled, with the (query, key) pairs each kind of layer
    sees for them."""
    cfg = run["config"]
    out = {"dec": 0, "pre": 0, "sampled": 0,
           "dec_pairs": {False: 0.0, True: 0.0},
           "pre_pairs": {False: 0.0, True: 0.0}}
    for r in run["requests"]:
        p = len(r.prompt)
        for i, s in enumerate(r.stamps):
            if not a <= s < b:
                continue
            out["sampled"] += 1
            if i == 0:
                out["pre"] += p
                for kind in (False, True):
                    out["pre_pairs"][kind] += visible_sum_prompt(cfg, p, kind)
            else:
                out["dec"] += 1
                for kind in (False, True):
                    out["dec_pairs"][kind] += visible(cfg, p + i, kind)
    return out


def _layers_of(config: dict, windowed: bool) -> int:
    return sum(1 for f in layers_kept(config)[0] if bool(f) == windowed)


def attention_flops(config: dict, pairs: dict) -> float:
    """QK^T and PV over the visible pairs of both kinds of layer."""
    per_pair = 4.0 * config["num_attention_heads"] * config["head_dim"]
    return per_pair * sum(_layers_of(config, kind) * pairs[kind]
                          for kind in (False, True))


def serve_flops(run: dict, a: float, b: float) -> float:
    """Model FLOPs of every token processed between ``a`` and ``b``: active
    parameters a token a layer, attention over the context each layer kind
    really sees, the head once a sampled position."""
    cfg = run["config"]
    t = tokens_by_kind(run, a, b)
    tokens = t["dec"] + t["pre"]
    pairs = {k: t["dec_pairs"][k] + t["pre_pairs"][k] for k in (False, True)}
    return (tokens * cfg["num_hidden_layers"] * layer_flops_per_token(cfg)
            + attention_flops(cfg, pairs)
            + t["sampled"] * head_flops(cfg))


# --------------------------------------------------------------------------- #
# traced operations                                                            #
# --------------------------------------------------------------------------- #

_BLOCK_REST = re.compile(r"(?:^|/)block_\d+/(.*)$")


def in_moe_experts(name: str, path: str, category: str) -> bool:
    m = _BLOCK_REST.search(path)
    return bool(m) and m.group(1).startswith("moe/experts")


def in_moe_rest(name: str, path: str, category: str) -> bool:
    m = _BLOCK_REST.search(path)
    return (bool(m) and m.group(1).startswith("moe/")
            and not m.group(1).startswith("moe/experts"))


def in_block_attention(name: str, path: str, category: str) -> bool:
    """Traced directly under ``block_N``: not inside a projection, a norm or
    the mixture layer, and not one of the block's residual additions."""
    m = _BLOCK_REST.search(path)
    if not m:
        return False
    rest = m.group(1)
    if re.match(r"(q_proj|k_proj|v_proj|o_proj|norm_\d+|moe)/", rest):
        return False
    return rest.rsplit("/", 1)[-1] != "add"


def _under_spans(tr, ops: list, span: str) -> float:
    """Seconds of the operations ``ops`` that start inside a host span of
    that name."""
    spans = tr.spans(span)
    starts = [s for s, _ in spans]
    total = 0.0
    for s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < spans[i][1]:
            total += e - s
    return total


_traced = readers._traced


def _traced_stretch(run: dict) -> tuple:
    tr = run["trace"]
    return tr.to_perf(tr.begin), tr.to_perf(tr.end)


# --------------------------------------------------------------------------- #
# the readers of metrics/*.shortlong.py                                        #
# --------------------------------------------------------------------------- #

def step_mfu_pct(run: dict):
    flops = serve_flops(run, run["t0"], run["t1"])
    if flops <= 0:
        return None
    peak = peaks.peak(run["device"]["kind"])["flops_per_s"]
    return 100.0 * flops / (run["seconds"] * peak)


def moe_roofline_pct(run: dict):
    """Roofline time of the expert products of the traced stretch over the
    device time under ``block_N/moe/experts``. A program's products read the
    weights of the experts hit once (a decode step of 128 rows x 6 hits all
    64 with near certainty, a prefill of thousands surely), and each
    assignment costs its expert's FLOPs."""
    tr = _traced(run)
    if tr is None:
        return None
    seconds = tr.op_seconds(in_moe_experts)
    if seconds <= 0:
        return None
    cfg = run["config"]
    a, b = _traced_stretch(run)
    t = tokens_by_kind(run, a, b)
    programs = len(tr.spans(readers.DECODE_SPAN)) + len(
        tr.spans(readers.PREFILL_SPAN))
    layers = cfg["num_hidden_layers"]
    need = {
        "flops": (t["dec"] + t["pre"]) * layers * 2.0
        * cfg["moe_num_active_primary_experts"] * _expert_params(cfg),
        "bytes": programs * layers * cfg["moe_num_primary_experts"]
        * _expert_params(cfg) * 2.0,
    }
    least = work.roofline_seconds(need, peaks.peak(run["device"]["kind"]))
    return 100.0 * least / seconds


def moe_dispatch_ms_per_step(run: dict):
    """Device time under ``block_N/moe`` outside the expert products (router,
    top-k, sort, gathers, the weighted sum), per decode span."""
    tr = _traced(run)
    if tr is None:
        return None
    spans = tr.spans(readers.DECODE_SPAN)
    ops = tr.ops_between(in_moe_rest)
    if not spans or not ops:
        return None
    return 1e3 * _under_spans(tr, ops, readers.DECODE_SPAN) / len(spans)


def _attention_roofline(run: dict, span: str, which: str):
    tr = _traced(run)
    if tr is None:
        return None
    ops = tr.ops_between(in_block_attention)
    if not tr.spans(span) or not ops:
        return None
    seconds = _under_spans(tr, ops, span)
    cfg, eng = run["config"], run["traffic"]["engine"]
    t = tokens_by_kind(run, *_traced_stretch(run))
    pairs, tokens = t[which + "_pairs"], t[which]
    if seconds <= 0 or tokens == 0:
        return None
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    qo = tokens * cfg["num_hidden_layers"] * 2.0 * h * dh * 2
    if which == "dec":
        # every visible K and V row once a query, in the store's type
        kv = kv_row_bytes(cfg, eng) * sum(
            _layers_of(cfg, kind) * pairs[kind] for kind in (False, True))
    else:
        # a prompt's K and V rows once, in the compute type, and written
        # once in the store's
        kv = tokens * cfg["num_hidden_layers"] * (
            2.0 * hk * dh * 2 + kv_row_bytes(cfg, eng))
    need = {"flops": attention_flops(cfg, pairs), "bytes": qo + kv}
    least = work.roofline_seconds(need, peaks.peak(run["device"]["kind"]))
    return 100.0 * least / seconds


def paged_decode_roofline_pct(run: dict):
    return _attention_roofline(run, readers.DECODE_SPAN, "dec")


def prefill_attention_roofline_pct(run: dict):
    return _attention_roofline(run, readers.PREFILL_SPAN, "pre")


def kv_pool_live_share_pct(run: dict):
    """Bytes live in both stores, averaged over the window, over the bytes
    of both pools: a request holds all its tokens in the full layers and at
    most the window's in the window layers."""
    cfg, eng = run["config"], run["traffic"]["engine"]
    t0, t1 = run["t0"], run["t1"]
    held = {False: 0.0, True: 0.0}
    for r in run["requests"]:
        st, p = r.stamps, len(r.prompt)
        if not st or st[0] >= t1:
            continue
        ends = st[1:] + ([st[-1]] if len(st) >= r.max_new else [t1])
        for i, (a, b) in enumerate(zip(st, ends)):
            dt = max(0.0, min(b, t1) - max(a, t0))
            for kind in (False, True):
                held[kind] += visible(cfg, p + i + 1, kind) * dt
    if held[False] <= 0:
        return None
    row, bs = kv_row_bytes(cfg, eng), eng["kv_block_size"]
    live = row * sum(_layers_of(cfg, k) * held[k] for k in (False, True))
    pools = row * bs * (
        _layers_of(cfg, False) * (eng["kv_blocks"] - 1)
        + _layers_of(cfg, True) * (eng["kv_window_blocks"] - 1))
    return 100.0 * live / ((t1 - t0) * pools)
