"""Plain reference for the Laguna layer as ``laguna-s-2.1-l5-ep2.json``
states it, one chip's share of the routed experts included.

Straight ``jax.numpy`` in float32 with ``precision=highest``: no kernels, no
cache, no batching tricks, no sorting. It imports nothing of the program and
is given weights the benchmark made. One layer ``i``, ``x_in [T, d]`` the
residual stream entering it, ``H_i = num_attention_heads_per_layer[i]``::

    a   = RMSNorm_1(x_in)
    q, k, v = a @ Wq [H_i, D], a @ Wk [Hkv, D], a @ Wv [Hkv, D]
    g   = sigmoid(a @ Wg) [H_i]                   gating per head
    full layer (layer_types[i] == "full_attention"):
        RoPE on entries 0..R-1 of each head, R = D * partial_rotary_factor,
        entry j pairing with j + R/2, YaRN's inverse frequencies, cos and sin
        times attention_factor; entries R..D-1 pass through
        visible(j | t) = j <= t
    sliding layer:
        RoPE over the whole head, theta 10000, no scaling
        visible(j | t) = t - sliding_window < j <= t
    o_h = g_h * softmax(q_h k^T / sqrt(D) over visible) v ; head h reads KV
          head h // (H_i / Hkv)
    x   = x_in + o @ Wo
    m   = RMSNorm_2(x)
    dense layer (mlp_layer_types[i] == "dense"):
        y = (silu(m @ Wgate) * (m @ Wup)) @ Wdown
    sparse layer:
        r   = m @ W_router  [num_experts published]
        top = the num_experts_per_tok largest of r
        w   = moe_routed_scaling_factor * softmax(r[top])
        y   = sum_{e in top, first <= e < first + count} w_e * expert_e(m)
              + shared(m)
        (experts and shared: the same gated form with SiLU)
    x_out = x + y

then a final RMSNorm and an untied head over the vocabulary rows held; no
bias anywhere. ``held_experts`` (``first``, ``count``) is the share of the
routed experts whose weights are here: the router still goes over all the
published experts, what an expert held elsewhere would add is left out,
and that partial stream goes on. Attention runs over blocks of queries and
the experts one at a time in a ``lax.scan`` (every token through each
expert, weighted by what the router gave it, zero where it was not chosen),
so that a sequence of the cell's ``cache_len`` fits on the chip beside the
weights. Sizes are read under the names the published ``config.json`` gives
them; of the per-layer lists the first ``num_hidden_layers`` entries are the
layers kept.

``lowp=True`` is the lower-precision control: every matmul operand is rounded
to float8_e4m3fn, scaled per row, before a float32 product, the router's and
the attention's among them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256


def _fp8(x):
    """Round to float8_e4m3fn and back, with one scale per row (last axis)."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _dot(x, w, lowp):
    """``x [..., k] @ w [k, n]`` in float32."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if lowp:
        x = _fp8(x)
        w = _fp8(w.T).T              # one scale per output column
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms_norm(x, p, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * p["scale"].astype(jnp.float32)


def yarn_inv_freq(rp: dict, dim: int):
    """YaRN's inverse frequencies over a rotary width ``dim``, as Hugging
    Face computes them (``truncate`` at its default, true)."""
    base, factor = float(rp["rope_theta"]), float(rp["factor"])
    orig = rp["original_max_position_embeddings"]
    j = jnp.arange(dim // 2, dtype=jnp.float32)
    pos_freqs = base ** (2.0 * j / dim)
    extrap, interp = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)

    def corr(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(corr(rp["beta_fast"])), 0)
    high = min(math.ceil(corr(rp["beta_slow"])), dim - 1)
    ramp = jnp.clip((j - low) / (high - low), 0.0, 1.0)
    return interp * ramp + extrap * (1.0 - ramp)


def _rope(x, rp: dict):
    """``x [T, heads, D]`` at positions ``0..T-1`` under one layer kind's
    ``rope_parameters``."""
    t, _, d = x.shape
    dim = int(d * rp["partial_rotary_factor"])
    half = dim // 2
    if rp["rope_type"] == "yarn":
        inv_freq, factor = yarn_inv_freq(rp, dim), rp["attention_factor"]
    else:
        inv_freq = float(rp["rope_theta"]) ** (
            -2.0 * jnp.arange(half, dtype=jnp.float32) / dim)
        factor = 1.0
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = factor * jnp.cos(ang)[:, None, :]
    sin = factor * jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:dim], x[..., dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _attention(q, k, v, gate, window, lowp):
    """``q [T, H, D]``, ``k, v [T, Hkv, D]``, ``gate [T, H]``; causal, and
    where ``window`` is set position ``t`` sees ``t - window < j <= t``. A
    block of queries at a time against all keys."""
    t, h, d = q.shape
    g = h // k.shape[1]
    if lowp:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    j = jnp.arange(t)
    out = []
    for lo in range(0, t, QUERY_BLOCK):
        qb = q[lo:lo + QUERY_BLOCK]                          # [Q, H, D]
        n = qb.shape[0]
        qb = qb.reshape(n, k.shape[1], g, d)
        s = jnp.einsum("qhgd,khd->hgqk", qb, k, precision=HIGHEST)
        s = s / jnp.sqrt(jnp.float32(d))
        i = (lo + jnp.arange(n))[:, None]
        seen = j[None, :] <= i
        if window is not None:
            seen = seen & (j[None, :] > i - window)
        s = jnp.where(seen[None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", w, v, precision=HIGHEST)
        o = o.reshape(n, h, d) * gate[lo:lo + QUERY_BLOCK, :, None]
        out.append(o.reshape(n, h * d))
    return jnp.concatenate(out, axis=0)


def _gated(x, w_gate, w_up, w_down, lowp):
    hidden = jax.nn.silu(_dot(x, w_gate, lowp)) * _dot(x, w_up, lowp)
    return _dot(hidden, w_down, lowp)


def _experts(p, m, cfg, lowp):
    """The routed sum over the experts held, for ``m [T, d]``."""
    first = cfg["held_experts"]["first"]
    r = _dot(m, p["router"], lowp)                           # [T, published]
    top, idx = jax.lax.top_k(r, cfg["num_experts_per_tok"])
    w = cfg["moe_routed_scaling_factor"] * jax.nn.softmax(top, axis=-1)

    def add_expert(y, xs):
        e, w_gate, w_up, w_down = xs
        share = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        return y + share[:, None] * _gated(m, w_gate, w_up, w_down,
                                           lowp), None

    # one expert at a time (a loop the compiler keeps rolled); expert e of
    # the published numbering is row e - first of the weights held
    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(m), (
        first + jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
        p["w_down"]))
    return y


def _layer(p, x, i, cfg, lowp):
    """One layer on ``x [T, d]``."""
    h, hk, dh = (cfg["num_attention_heads_per_layer"][i],
                 cfg["num_key_value_heads"], cfg["head_dim"])
    t = x.shape[0]
    kind = cfg["layer_types"][i]
    rp = cfg["rope_parameters"][kind]
    a = _rms_norm(x, p["norm_1"], cfg["rms_norm_eps"])
    q = _dot(a, p["q_proj"]["kernel"], lowp).reshape(t, h, dh)
    k = _dot(a, p["k_proj"]["kernel"], lowp).reshape(t, hk, dh)
    v = _dot(a, p["v_proj"]["kernel"], lowp).reshape(t, hk, dh)
    gate = jax.nn.sigmoid(_dot(a, p["g_proj"]["kernel"], lowp))
    q, k = _rope(q, rp), _rope(k, rp)
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    o = _attention(q, k, v, gate, window, lowp)
    x = x + _dot(o, p["o_proj"]["kernel"], lowp)
    m = _rms_norm(x, p["norm_2"], cfg["rms_norm_eps"])
    if cfg["mlp_layer_types"][i] == "dense":
        mlp = p["mlp"]
        return x + _gated(m, mlp["gate_proj"]["kernel"],
                          mlp["up_proj"]["kernel"],
                          mlp["down_proj"]["kernel"], lowp)
    shared = p["moe"]["shared"]
    y = _experts(p["moe"], m, cfg, lowp) + _gated(
        m, shared["gate_proj"]["kernel"], shared["up_proj"]["kernel"],
        shared["down_proj"]["kernel"], lowp)
    return x + y


def hidden(params, tokens, cfg, lowp=False):
    """Final-norm hidden states ``[B, T, d]`` for ``tokens [B, T]`` at
    positions ``0..T-1``, a row at a time."""
    p = params["params"]
    rows = []
    for row in tokens:
        x = p["embed"]["embedding"][row].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            x = _layer(p[f"block_{i}"], x, i, cfg, lowp)
        rows.append(_rms_norm(x, p["norm"], cfg["rms_norm_eps"]))
    return jnp.stack(rows)


def logits(params, tokens, cfg, lowp=False):
    """``[B, T, vocab held]`` float32 logits. The head takes the last
    position apart from those before it: a caller that reads ``[:, :-1]``
    (every position that predicts a token it holds) then reads the first
    product as it stands, where a slice of one product would be a second
    array of the size."""
    x = hidden(params, tokens, cfg, lowp=lowp)
    head = params["params"]["lm_head"]["kernel"]
    return jnp.concatenate([_dot(x[:, :-1], head, lowp),
                            _dot(x[:, -1:], head, lowp)], axis=1)
