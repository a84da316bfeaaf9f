"""``chainermn_tpu.models.LagunaLM`` from a configuration that keeps the
published ``config.json`` key names and states this chip's share
(``held_experts``, the vocabulary rows held), what its serving step costs by
the mathematics (FLOPs of the weights a token really meets, layer by layer,
attention over what each layer kind sees with that layer's query heads, bytes
of the experts held and of the K/V rows visible), and which traced device
operations are its attention, its expert products, its shared expert and the
rest of its mixture layer. The metric files ``metrics/*.shortlong.py`` read a
run's record through the functions at the end; what is not this family's own
(spans, the store's row, the mark for a kernel) is the SmallThinker family's.
"""

from __future__ import annotations

import re

from harness import common, families, peaks, readers, work

_st = common.load_module("harness", "families", "smallthinker.py")

FULL, SLIDING = "full_attention", "sliding_attention"


# --------------------------------------------------------------------------- #
# the model                                                                    #
# --------------------------------------------------------------------------- #

def layers_kept(config: dict) -> list:
    """``(windowed, query heads, dense)`` of each layer kept: the first
    ``num_hidden_layers`` entries of the published per-layer lists."""
    n = config["num_hidden_layers"]
    return [(config["layer_types"][i] == SLIDING,
             config["num_attention_heads_per_layer"][i],
             config["mlp_layer_types"][i] == "dense") for i in range(n)]


def _positions(rp: dict) -> tuple:
    head = (rp["rope_type"], float(rp["rope_theta"]),
            float(rp["partial_rotary_factor"]))
    if rp["rope_type"] != "yarn":
        return head
    return head + (float(rp["factor"]),
                   rp["original_max_position_embeddings"],
                   float(rp["beta_fast"]), float(rp["beta_slow"]),
                   float(rp["attention_factor"]))


def build_model(config: dict, **kw):
    from chainermn_tpu.models import LagunaLM

    kept = layers_kept(config)
    held = config["held_experts"]
    if held["count"] != config["num_experts"]:
        raise ValueError("num_experts counts the experts held here")
    return LagunaLM(
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_kv_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"], n_layers=len(kept),
        heads_per_layer=tuple(h for _, h, _ in kept),
        window=config["sliding_window"],
        window_layers=tuple(w for w, _, _ in kept),
        dense_layers=tuple(d for _, _, d in kept),
        dense_d_ff=config["intermediate_size"],
        d_ff=config["moe_intermediate_size"],
        n_experts=held["published"], top_k=config["num_experts_per_tok"],
        held_experts=(held["first"], held["count"]),
        routed_scale=float(config["moe_routed_scaling_factor"]),
        shared_d_ff=config["shared_expert_intermediate_size"],
        rope_full=_positions(config["rope_parameters"][FULL]),
        rope_window=_positions(config["rope_parameters"][SLIDING]),
        rms_norm_eps=config["rms_norm_eps"],
        max_len=config["max_position_embeddings"],
        compute_dtype=families.dtype(config["compute_dtype"]), **kw)


def init_shapes(config: dict, model):
    """The model's own tree of shapes. The experts' down projections
    ``[held, d_ff, d_model]`` are marked as a kernel, which draws them at
    1/sqrt(128 x 1024) = 0.0028 and not at 0.02: the configuration's
    ``assumed`` says why (a choice of this benchmark, not the program's)."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    for name, block in shapes.items():
        if name.startswith("block_") and "moe" in block:
            block["moe"]["w_down"] = _st._AsKernel(block["moe"]["w_down"])
    return {"params": shapes}


# --------------------------------------------------------------------------- #
# what the mathematics asks for                                                #
# --------------------------------------------------------------------------- #

def attn_params(config: dict, heads: int) -> int:
    """q, k, v, o and the per-head gate of a layer with ``heads`` query
    heads."""
    d, dh, hk = (config["hidden_size"], config["head_dim"],
                 config["num_key_value_heads"])
    return 2 * d * heads * dh + 2 * d * hk * dh + d * heads


def expert_params(config: dict) -> int:
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def layer_params(config: dict, i: int) -> int:
    """Parameters of layer ``i`` as held here (two norms included)."""
    _, heads, dense = layers_kept(config)[i]
    d = config["hidden_size"]
    n = attn_params(config, heads) + 2 * d
    if dense:
        return n + 3 * d * config["intermediate_size"]
    return (n + d * config["held_experts"]["published"]
            + 3 * d * config["shared_expert_intermediate_size"]
            + config["num_experts"] * expert_params(config))


def total_params(config: dict) -> int:
    d = config["hidden_size"]
    return (sum(layer_params(config, i)
                for i in range(config["num_hidden_layers"]))
            + 2 * config["vocab_size"] * d + d)


def held_share(config: dict) -> float:
    """The share of a token's routed assignments that an expert held here
    takes, under near-uniform routing: an expectation, which
    ``moe_local_share`` of the program's own counters measures."""
    return config["num_experts"] / config["held_experts"]["published"]


def routed_flops_per_token(config: dict) -> float:
    return (2.0 * config["num_experts_per_tok"] * held_share(config)
            * expert_params(config))


def matmul_flops_per_token(config: dict) -> float:
    """Forward FLOPs of one token through the weights it really meets in
    all the layers kept: attention projections and gate, the dense layer,
    the router over its published width, the shared expert, and the routed
    experts held here (expected). 2 per weight."""
    d, total = config["hidden_size"], 0.0
    for _, heads, dense in layers_kept(config):
        total += 2.0 * attn_params(config, heads)
        if dense:
            total += 2.0 * 3 * d * config["intermediate_size"]
        else:
            total += (2.0 * d * config["held_experts"]["published"]
                      + 2.0 * 3 * d
                      * config["shared_expert_intermediate_size"]
                      + routed_flops_per_token(config))
    return total


def head_flops(config: dict) -> float:
    return 2.0 * config["hidden_size"] * config["vocab_size"]


def _st_view(config: dict) -> dict:
    """The configuration under the one key name that differs where the
    SmallThinker family's counts of visible keys read the window."""
    return dict(config, sliding_window_size=config["sliding_window"])


def visible(config: dict, context: float, windowed: bool) -> float:
    """Keys a query with ``context`` positions up to its own sees."""
    return _st.visible(_st_view(config), context, windowed)


def visible_sum_prompt(config: dict, p: int, windowed: bool) -> float:
    """Visible (query, key) pairs of a causal prompt of ``p`` tokens."""
    return _st.visible_sum_prompt(_st_view(config), p, windowed)


def tokens_by_kind(run: dict, a: float, b: float) -> dict:
    """From the harness's stamps between ``a`` and ``b``: tokens decoded and
    prompt tokens prefilled, with the (query, key) pairs each kind of layer
    sees for them."""
    return _st.tokens_by_kind(dict(run, config=_st_view(run["config"])), a, b)


def heads_of_kind(config: dict, windowed: bool) -> int:
    """Query heads summed over the layers of a kind."""
    return sum(h for w, h, _ in layers_kept(config) if w == windowed)


def layers_of_kind(config: dict, windowed: bool) -> int:
    return sum(1 for w, _, _ in layers_kept(config) if w == windowed)


def sparse_layers(config: dict) -> int:
    return sum(1 for _, _, dense in layers_kept(config) if not dense)


def attention_flops(config: dict, pairs: dict) -> float:
    """QK^T and PV over the visible pairs, each layer with its own heads."""
    return 4.0 * config["head_dim"] * sum(
        heads_of_kind(config, kind) * pairs[kind] for kind in (False, True))


def serve_flops(run: dict, a: float, b: float) -> float:
    cfg = run["config"]
    t = tokens_by_kind(run, a, b)
    pairs = {k: t["dec_pairs"][k] + t["pre_pairs"][k] for k in (False, True)}
    return ((t["dec"] + t["pre"]) * matmul_flops_per_token(cfg)
            + attention_flops(cfg, pairs) + t["sampled"] * head_flops(cfg))


# --------------------------------------------------------------------------- #
# traced operations                                                            #
# --------------------------------------------------------------------------- #

in_moe_experts = _st.in_moe_experts


def _rest(path: str) -> str:
    m = _st._BLOCK_REST.search(path)
    return m.group(1) if m else ""


def in_moe_shared(name: str, path: str, category: str) -> bool:
    return _rest(path).startswith("moe/shared/")


def in_moe_rest(name: str, path: str, category: str) -> bool:
    """Under ``block_N/moe`` outside the products and the shared expert:
    router, top-k, sort, gathers, the weighted sum."""
    rest = _rest(path)
    return rest.startswith("moe/") and not rest.startswith(
        ("moe/experts", "moe/shared/"))


def in_dense_mlp(name: str, path: str, category: str) -> bool:
    return _rest(path).startswith("mlp/")


def in_block_attention(name: str, path: str, category: str) -> bool:
    """Traced directly under ``block_N``: not inside a projection, a norm,
    the dense layer or the mixture layer, and not one of the block's
    residual additions. RoPE, the cache write and the per-head gate's
    product count with it."""
    rest = _rest(path)
    if not rest or re.match(
            r"(q_proj|k_proj|v_proj|g_proj|o_proj|norm_\d+|moe|mlp)/", rest):
        return False
    return rest.rsplit("/", 1)[-1] != "add"


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
    weights of the experts held once (a decode step of 128 rows x 10 hits
    all 128 with near certainty), and each assignment to an expert held
    costs its FLOPs (half of all, expected)."""
    tr = _st._traced(run)
    if tr is None:
        return None
    seconds = tr.op_seconds(in_moe_experts)
    if seconds <= 0:
        return None
    cfg = run["config"]
    t = tokens_by_kind(run, *_st._traced_stretch(run))
    programs = len(tr.spans(readers.DECODE_SPAN)) + len(
        tr.spans(readers.PREFILL_SPAN))
    layers = sparse_layers(cfg)
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
    return 1e3 * _st._under_spans(tr, ops, readers.DECODE_SPAN) / len(spans)


def moe_dispatch_ms_per_step(run: dict):
    """Device time under ``block_N/moe`` outside the expert products and
    the shared expert, per decode span."""
    return _ms_per_decode_span(run, in_moe_rest)


def moe_shared_ms_per_step(run: dict):
    """Device time of the operations traced under ``block_N/moe/shared``
    inside decode spans, per decode span."""
    return _ms_per_decode_span(run, in_moe_shared)


def _attention_roofline(run: dict, span: str, which: str):
    tr = _st._traced(run)
    if tr is None:
        return None
    ops = tr.ops_between(in_block_attention)
    if not tr.spans(span) or not ops:
        return None
    seconds = _st._under_spans(tr, ops, span)
    cfg, eng = run["config"], run["traffic"]["engine"]
    t = tokens_by_kind(run, *_st._traced_stretch(run))
    pairs, tokens = t[which + "_pairs"], t[which]
    if seconds <= 0 or tokens == 0:
        return None
    hk, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    heads = sum(h for _, h, _ in layers_kept(cfg))
    qo = tokens * 2.0 * heads * dh * 2
    row = _st.kv_row_bytes(cfg, eng)
    if which == "dec":
        # every visible K and V row once a query, in the store's type
        kv = row * sum(layers_of_kind(cfg, kind) * pairs[kind]
                       for kind in (False, True))
    else:
        # a prompt's K and V rows once, in the compute type, and written
        # once in the store's
        kv = tokens * cfg["num_hidden_layers"] * (2.0 * hk * dh * 2 + row)
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
    bs = eng["kv_block_size"]
    live = sum(layers_of_kind(cfg, k) * held[k] for k in (False, True))
    pools = bs * (layers_of_kind(cfg, False) * (eng["kv_blocks"] - 1)
                  + layers_of_kind(cfg, True)
                  * (eng["kv_window_blocks"] - 1))
    return 100.0 * live / ((t1 - t0) * pools)
