"""``chainermn_tpu.models.Qwen3NextLM`` from a configuration that keeps the
published ``config.json`` key names and states this chip's share
(``held_experts``, the vocabulary rows held), what its serving step costs by
the mathematics (FLOPs of the weights a token really meets, the gated delta
rule's own, attention over what the full layers see; bytes of the experts
held, of the recurrent state read and written, of the K/V rows visible), and
which traced device operations are its linear-attention layer's recurrence,
its attention, its expert products and the rest of its mixture layer. The
metric files ``metrics/*.shortlong.py`` read a run's record through the
functions at the end; what is not this family's own is the SmallThinker
family's (spans, the store's row, the mark for a kernel) and the Laguna
family's (which operations are the shared expert and the rest of the mixture
layer).
"""

from __future__ import annotations

import re

from harness import common, families, peaks, readers, trace, work

_st = common.load_module("harness", "families", "smallthinker.py")
_lg = common.load_module("harness", "families", "laguna.py")


# --------------------------------------------------------------------------- #
# the model                                                                    #
# --------------------------------------------------------------------------- #

def linear_layers(config: dict) -> list:
    """The layers kept that are Gated DeltaNet layers."""
    return [i for i in range(config["num_hidden_layers"])
            if (i + 1) % config["full_attention_interval"]]


def n_linear(config: dict) -> int:
    return len(linear_layers(config))


def n_full(config: dict) -> int:
    return config["num_hidden_layers"] - n_linear(config)


def build_model(config: dict, **kw):
    from chainermn_tpu.models import Qwen3NextLM

    held = config["held_experts"]
    if held["count"] != config["num_experts"]:
        raise ValueError("num_experts counts the experts held here")
    return Qwen3NextLM(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layers=config["num_hidden_layers"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        linear_k_heads=config["linear_num_key_heads"],
        linear_v_heads=config["linear_num_value_heads"],
        linear_k_dim=config["linear_key_head_dim"],
        linear_v_dim=config["linear_value_head_dim"],
        conv_kernel=config["linear_conv_kernel_dim"],
        full_attention_interval=config["full_attention_interval"],
        partial_rotary_factor=float(config["partial_rotary_factor"]),
        rope_theta=float(config["rope_theta"]),
        d_ff=config["moe_intermediate_size"],
        n_experts=held["published"], top_k=config["num_experts_per_tok"],
        held_experts=(held["first"], held["count"]),
        shared_d_ff=config["shared_expert_intermediate_size"],
        rms_norm_eps=config["rms_norm_eps"],
        max_len=config["max_position_embeddings"],
        compute_dtype=families.dtype(config["compute_dtype"]), **kw)


def init_shapes(config: dict, model):
    """The model's own tree of shapes. The short convolution's weights ``[K,
    channels]`` are a plain parameter of the program; marked as a kernel they
    are drawn at 1/sqrt(K) and not at 0.02, so that the convolution keeps
    its input's variance. The experts' down projections ``[held, d_ff,
    d_model]`` are marked too, which draws them at 1/sqrt(256 x 512) =
    0.0028 and not at 0.02 (PR 29's remedy). The configuration's ``assumed``
    says why of both (choices of this benchmark, not the program's)."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    for name, block in shapes.items():
        if not name.startswith("block_"):
            continue
        block["moe"]["w_down"] = _st._AsKernel(block["moe"]["w_down"])
        if "gdn" in block:
            block["gdn"]["conv_kernel"] = _st._AsKernel(
                block["gdn"]["conv_kernel"])
    return {"params": shapes}


# --------------------------------------------------------------------------- #
# what the mathematics asks for                                                #
# --------------------------------------------------------------------------- #

def _linear_sizes(config: dict) -> tuple:
    """``(Hk, Hv, dk, dv, K)`` of a Gated DeltaNet layer."""
    return (config["linear_num_key_heads"], config["linear_num_value_heads"],
            config["linear_key_head_dim"], config["linear_value_head_dim"],
            config["linear_conv_kernel_dim"])


def gdn_params(config: dict) -> int:
    """in_proj (q, k, v, z and b, a), the convolution, A_log and dt_bias,
    the gated norm's scale, out_proj."""
    d = config["hidden_size"]
    hk, hv, dk, dv, kk = _linear_sizes(config)
    key_dim, value_dim = hk * dk, hv * dv
    return (d * (2 * key_dim + 2 * value_dim) + d * 2 * hv
            + (2 * key_dim + value_dim) * kk + 2 * hv + dv + value_dim * d)


def attn_params(config: dict) -> int:
    """q with its gate, k, v, o, and the two per-head norms."""
    d, dh = config["hidden_size"], config["head_dim"]
    h, hk = config["num_attention_heads"], config["num_key_value_heads"]
    return 2 * d * h * dh + 2 * d * hk * dh + h * dh * d + 2 * dh


def expert_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layer_rest_params(config: dict) -> int:
    """Router over its published width, shared expert, its gate, two
    norms."""
    d = config["hidden_size"]
    return (d * config["held_experts"]["published"]
            + 3 * d * config["shared_expert_intermediate_size"] + d + 2 * d)


def total_params(config: dict) -> int:
    d = config["hidden_size"]
    per_layer = layer_rest_params(config) + config["num_experts"] * (
        expert_params(config))
    return (n_linear(config) * (gdn_params(config) + per_layer)
            + n_full(config) * (attn_params(config) + per_layer)
            + 2 * config["vocab_size"] * d + d)


def held_share(config: dict) -> float:
    """The share of a token's routed assignments that an expert held here
    takes, under near-uniform routing: an expectation, which
    ``moe_local_share`` of the program's own counters measures."""
    return config["num_experts"] / config["held_experts"]["published"]


def routed_flops_per_token(config: dict) -> float:
    return (2.0 * config["num_experts_per_tok"] * held_share(config)
            * expert_params(config))


def state_bytes(config: dict) -> float:
    """One request's recurrent state in one linear layer, float32."""
    _, hv, dk, dv, _ = _linear_sizes(config)
    return 4.0 * hv * dk * dv


def conv_state_bytes(config: dict) -> float:
    """The convolution's last ``K - 1`` inputs of one request in one linear
    layer, in the compute type's 2 bytes."""
    hk, hv, dk, dv, kk = _linear_sizes(config)
    return 2.0 * (kk - 1) * (2 * hk * dk + hv * dv)


def recurrence_flops_per_token(config: dict) -> float:
    """The gated delta rule's own FLOPs for one token in one linear layer,
    whatever the chunk: per value head ``S^T k`` (2 dk dv), the decay and
    the rank-one update (3 dk dv) and ``S^T q`` (2 dk dv)."""
    _, hv, dk, dv, _ = _linear_sizes(config)
    return 7.0 * hv * dk * dv


def recurrence_token_bytes(config: dict) -> float:
    """What one token brings to and takes from the recurrence in one linear
    layer, in float32: q and k a key head, v and o a value head, g and
    beta."""
    hk, hv, dk, dv, _ = _linear_sizes(config)
    return 4.0 * (2 * hk * dk + 2 * hv * dv + 2 * hv)


def matmul_flops_per_token(config: dict) -> float:
    """Forward FLOPs of one token through the weights it really meets in
    all the layers kept: both mixers' projections, the convolution, the
    router over its published width, the shared expert and its gate, and
    the routed experts held here (expected). 2 per weight; the norms'
    scales and A_log, dt_bias multiply nothing."""
    d = config["hidden_size"]
    hk, hv, dk, dv, kk = _linear_sizes(config)
    gdn = 2.0 * (gdn_params(config) - 2 * hv - dv)
    attn = 2.0 * (attn_params(config) - 2 * config["head_dim"])
    rest = (2.0 * (layer_rest_params(config) - 2 * d)
            + routed_flops_per_token(config))
    return (n_linear(config) * (gdn + rest) + n_full(config) * (attn + rest))


def head_flops(config: dict) -> float:
    return 2.0 * config["hidden_size"] * config["vocab_size"]


def tokens_seen(run: dict, a: float, b: float) -> dict:
    """From the harness's stamps between ``a`` and ``b``: tokens decoded,
    prompt tokens prefilled and prompts begun, with the (query, key) pairs a
    full layer sees for them (``False``: no window)."""
    view = dict(run["config"],
                sliding_window_size=run["config"]["max_position_embeddings"])
    t = _st.tokens_by_kind(dict(run, config=view), a, b)
    t["prompts"] = sum(1 for r in run["requests"]
                       if r.stamps and a <= r.stamps[0] < b)
    return t


def attention_flops(config: dict, pairs: float) -> float:
    """QK^T and PV over the visible pairs of the full layers."""
    return (4.0 * config["num_attention_heads"] * config["head_dim"]
            * n_full(config) * pairs)


def serve_flops(run: dict, a: float, b: float) -> float:
    cfg = run["config"]
    t = tokens_seen(run, a, b)
    tokens = t["dec"] + t["pre"]
    pairs = t["dec_pairs"][False] + t["pre_pairs"][False]
    return (tokens * (matmul_flops_per_token(cfg)
                      + n_linear(cfg) * recurrence_flops_per_token(cfg))
            + attention_flops(cfg, pairs) + t["sampled"] * head_flops(cfg))


# --------------------------------------------------------------------------- #
# traced operations                                                            #
# --------------------------------------------------------------------------- #

in_moe_experts = _st.in_moe_experts
in_moe_shared, in_moe_rest, _rest = (_lg.in_moe_shared, _lg.in_moe_rest,
                                     _lg._rest)


def in_gdn(name: str, path: str, category: str) -> bool:
    return _rest(path).startswith("gdn/")


def in_gdn_recurrence(name: str, path: str, category: str) -> bool:
    return _rest(path).startswith("gdn/recurrence")


def in_block_attention(name: str, path: str, category: str) -> bool:
    """Under ``block_N/attn`` outside its projections and norms: RoPE, the
    cache write, the kernel, the output gate's product."""
    rest = _rest(path)
    return rest.startswith("attn/") and not re.match(
        r"attn/(q_proj|k_proj|v_proj|o_proj|q_norm|k_norm)/", rest)


def _union_under_spans(tr, ops: list, span: str) -> float:
    """Seconds in which one of the operations ``ops`` that start inside a
    host span of that name ran: the union of their intervals, so that a
    loop and the operations of its body count once."""
    spans = tr.spans(span)
    inside, i = [], 0
    for s, e in sorted(ops):
        while i < len(spans) and spans[i][1] <= s:
            i += 1
        if i < len(spans) and spans[i][0] <= s:
            inside.append((s, e))
    return sum(e - s for s, e in trace.merged(inside))


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
    """Roofline time of the routed experts' products of the traced stretch
    over the device time under ``block_N/moe/experts``: a program reads the
    weights of the experts held once (a decode step of 256 rows x 10 hits
    all 256 with near certainty), and each assignment to an expert held
    costs its FLOPs (half of all, expected)."""
    tr = _st._traced(run)
    if tr is None:
        return None
    seconds = tr.op_seconds(in_moe_experts)
    if seconds <= 0:
        return None
    cfg = run["config"]
    t = tokens_seen(run, *_st._traced_stretch(run))
    programs = len(tr.spans(readers.DECODE_SPAN)) + len(
        tr.spans(readers.PREFILL_SPAN))
    layers = cfg["num_hidden_layers"]
    need = {
        "flops": (t["dec"] + t["pre"]) * layers
        * routed_flops_per_token(cfg),
        "bytes": programs * layers * cfg["num_experts"]
        * expert_params(cfg) * 2.0,
    }
    least = work.roofline_seconds(need, peaks.peak(run["device"]["kind"]))
    return 100.0 * least / seconds


def _ms_per_decode_span(run: dict, which):
    tr = _st._traced(run)
    if tr is None:
        return None
    spans = tr.spans(readers.DECODE_SPAN)
    ops = tr.ops_between(which)
    if not spans or not ops:
        return None
    return 1e3 * _union_under_spans(tr, ops, readers.DECODE_SPAN) / len(spans)


def moe_dispatch_ms_per_step(run: dict):
    """Device time under ``block_N/moe`` outside the expert products and
    the shared expert, per decode span."""
    return _ms_per_decode_span(run, in_moe_rest)


def moe_shared_ms_per_step(run: dict):
    """Device time of the operations traced under ``block_N/moe/shared``
    (the shared expert and its gate) inside decode spans, per decode
    span."""
    return _ms_per_decode_span(run, in_moe_shared)


def gdn_ms_per_step(run: dict):
    """Device time under ``block_N/gdn`` (projections, convolution,
    recurrence, gated norm) inside decode spans, per decode span."""
    return _ms_per_decode_span(run, in_gdn)


def _gdn_roofline(run: dict, span: str, need_of):
    tr = _st._traced(run)
    if tr is None:
        return None
    ops = tr.ops_between(in_gdn_recurrence)
    if not tr.spans(span) or not ops:
        return None
    seconds = _union_under_spans(tr, ops, span)
    need = need_of(run["config"], tokens_seen(run, *_st._traced_stretch(run)))
    if seconds <= 0 or need is None:
        return None
    least = work.roofline_seconds(need, peaks.peak(run["device"]["kind"]))
    return 100.0 * least / seconds


def gdn_decode_work(cfg: dict, t: dict):
    """A decoded token reads its slot's state once and writes it once in
    every linear layer, beside what it brings and takes (q, k, v, g, beta,
    o)."""
    if not t["dec"]:
        return None
    per = 2.0 * state_bytes(cfg) + recurrence_token_bytes(cfg)
    return {"flops": t["dec"] * n_linear(cfg)
            * recurrence_flops_per_token(cfg),
            "bytes": t["dec"] * n_linear(cfg) * per}


def gdn_prefill_work(cfg: dict, t: dict):
    """A prompt's tokens each bring and take their operands once, and the
    prompt's final state is written once, in every linear layer; the
    recurrence's own FLOPs whatever the chunk."""
    if not t["pre"]:
        return None
    return {"flops": t["pre"] * n_linear(cfg)
            * recurrence_flops_per_token(cfg),
            "bytes": n_linear(cfg) * (
                t["pre"] * recurrence_token_bytes(cfg)
                + t["prompts"] * state_bytes(cfg))}


def gdn_decode_roofline_pct(run: dict):
    """Roofline time of the recurrence of the traced stretch's decoded
    tokens over the device time under ``block_N/gdn/recurrence`` inside
    decode spans."""
    return _gdn_roofline(run, readers.DECODE_SPAN, gdn_decode_work)


def gdn_prefill_roofline_pct(run: dict):
    """The same of the prompts prefilled, inside prefill spans."""
    return _gdn_roofline(run, readers.PREFILL_SPAN, gdn_prefill_work)


def _attention_roofline(run: dict, span: str, which: str):
    tr = _st._traced(run)
    if tr is None:
        return None
    ops = tr.ops_between(in_block_attention)
    if not tr.spans(span) or not ops:
        return None
    seconds = _union_under_spans(tr, ops, span)
    cfg, eng = run["config"], run["traffic"]["engine"]
    t = tokens_seen(run, *_st._traced_stretch(run))
    pairs, tokens = t[which + "_pairs"][False], t[which]
    if seconds <= 0 or tokens == 0:
        return None
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    layers = n_full(cfg)
    # the query and the output in the compute type, and the gate read
    qo = tokens * layers * 3.0 * h * dh * 2
    row = _st.kv_row_bytes(cfg, eng)
    if which == "dec":
        # every visible K and V row once a query, in the store's type
        kv = row * layers * pairs
    else:
        # a prompt's K and V rows once, in the compute type, and written
        # once in the store's
        kv = tokens * layers * (2.0 * hk * dh * 2 + row)
    need = {"flops": attention_flops(cfg, pairs), "bytes": qo + kv}
    least = work.roofline_seconds(need, peaks.peak(run["device"]["kind"]))
    return 100.0 * least / seconds


def paged_decode_roofline_pct(run: dict):
    return _attention_roofline(run, readers.DECODE_SPAN, "dec")


def prefill_attention_roofline_pct(run: dict):
    return _attention_roofline(run, readers.PREFILL_SPAN, "pre")


def kv_pool_live_share_pct(run: dict):
    """Bytes live in the full layers' block store and in the linear layers'
    state store, averaged over the window, over the bytes of both: a
    request holds all its tokens' K and V rows in the full layers, and from
    its first token to its last one row of the state store (the recurrent
    state and the convolution's last inputs) in every linear layer."""
    cfg, eng = run["config"], run["traffic"]["engine"]
    t0, t1 = run["t0"], run["t1"]
    rows, slot_seconds = 0.0, 0.0
    for r in run["requests"]:
        st, p = r.stamps, len(r.prompt)
        if not st or st[0] >= t1:
            continue
        ends = st[1:] + ([st[-1]] if len(st) >= r.max_new else [t1])
        for i, (a, b) in enumerate(zip(st, ends)):
            dt = max(0.0, min(b, t1) - max(a, t0))
            rows += (p + i + 1) * dt
            slot_seconds += dt
    if rows <= 0:
        return None
    row = _st.kv_row_bytes(cfg, eng)
    slot = n_linear(cfg) * (state_bytes(cfg) + conv_state_bytes(cfg))
    live = row * n_full(cfg) * rows + slot * slot_seconds
    held = (row * n_full(cfg) * eng["kv_block_size"] * (eng["kv_blocks"] - 1)
            + slot * eng["n_slots"])
    return 100.0 * live / ((t1 - t0) * held)
