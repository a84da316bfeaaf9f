"""Operations and bytes that the algorithm needs, from shapes alone. These are
the yardstick's counts: what the mathematics asks for, not what an
implementation happens to read or recompute."""

from __future__ import annotations


def lm_matmul_flops_per_token(cfg: dict) -> float:
    """Forward FLOPs of one token through every weight matrix of the decoder
    (qkv, proj, the two MLP matrices in each layer, and the head): 2 per
    weight. Embedding look-ups are not matmuls and count nothing."""
    d, ff, layers = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    per_layer = 2 * (d * 3 * d + d * d + 2 * d * ff)
    return float(layers * per_layer + 2 * d * cfg["vocab_size"])


def lm_attention_flops(cfg: dict, context: float) -> float:
    """Forward FLOPs of one token attending to ``context`` keys in every
    layer: QK^T and PV, 2 FLOPs per multiply-add each."""
    return float(cfg["n_layers"] * 4 * cfg["d_model"] * context)


def lm_forward_flops(cfg: dict, tokens: float, context_sum: float) -> float:
    """Forward FLOPs of ``tokens`` tokens whose contexts add up to
    ``context_sum`` (for a whole causal sequence of T: T(T+1)/2)."""
    return (tokens * lm_matmul_flops_per_token(cfg)
            + lm_attention_flops(cfg, context_sum))


def lm_train_flops_per_step(cfg: dict, batch: int, seq: int) -> float:
    """Forward and backward (2x the forward) of ``batch`` causal sequences of
    ``seq`` tokens; recomputation counts nothing."""
    ctx = batch * seq * (seq + 1) / 2.0
    return 3.0 * lm_forward_flops(cfg, batch * seq, ctx)


def causal_attention_train(cfg: dict, batch: int, seq: int) -> dict:
    """Causal attention, forward and backward, over all layers: FLOPs (the
    forward's two products and the backward's four, over the causal half) and
    the bytes that must cross HBM at least once (q, k, v, o read or written
    in the forward; q, k, v, o, do read and dq, dk, dv written in the
    backward), in the compute type's 2 bytes."""
    d, layers = cfg["d_model"], cfg["n_layers"]
    pairs = batch * seq * (seq + 1) / 2.0
    flops = layers * (4 + 8) * d * pairs
    elems = batch * seq * d
    bytes_ = layers * (4 + 8) * elems * 2
    return {"flops": float(flops), "bytes": float(bytes_)}


def paged_decode_attention(cfg: dict, engine: dict, context_sum: float,
                           tokens: float) -> dict:
    """Decode attention of ``tokens`` single-token steps whose live contexts
    add up to ``context_sum``, over all layers: each live token's K and V row
    read once in the store's type (int8 rows carry one float32 scale per head
    for K and one for V), the query read and the output written in the
    compute type."""
    d, layers, heads = cfg["d_model"], cfg["n_layers"], cfg["n_heads"]
    if engine.get("kv_quant") == "int8":
        row = 2 * (d * 1 + heads * 4)
    else:
        row = 2 * d * 2
    bytes_ = layers * (context_sum * row + tokens * 2 * d * 2)
    flops = layers * 4 * d * context_sum
    return {"flops": float(flops), "bytes": float(bytes_)}


def roofline_seconds(work: dict, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over peak
    FLOP/s and bytes over peak bytes/s."""
    return max(work["flops"] / peak["flops_per_s"],
               work["bytes"] / peak["bytes_per_s"])
