"""Plain reference for the SmallThinker layer as
``smallthinker-21b-a3b-l8.json`` states it.

Straight ``jax.numpy`` in float32 with ``precision=highest``: no kernels, no
cache, no batching tricks, no sorting. It imports nothing of the program and
is given weights the benchmark made. One layer, ``x_in`` the residual stream
entering it::

    r   = x_in @ W_router                  # the router reads the layer's INPUT
    a   = RMSNorm_1(x_in)
    q, k, v = a @ Wq [H, D], a @ Wk [Hkv, D], a @ Wv [Hkv, D]
    q, k = RoPE(q, k)  where rope_layout[i] == 1   (whole head, rotate-half)
    visible(j | t) = j <= t                       sliding_window_layout[i] == 0
                   = t - window < j <= t          sliding_window_layout[i] == 1
    o   = softmax(q k^T / sqrt(D) over visible) v ; head g reads KV head g // G
    x   = x_in + o @ Wo
    m   = RMSNorm_2(x)
    top = the top_k largest of r ;  w = softmax(r[top])
    y   = sum_{e in top} w_e * ((relu(m @ Wgate_e) * (m @ Wup_e)) @ Wdown_e)
    x_out = x + y

then a final RMSNorm and an untied head; no bias anywhere. Attention runs
over blocks of queries and the experts one at a time (every token through
each expert, weighted by what the router gave it, zero where it was not
chosen), so that a sequence of the cell's ``cache_len`` fits on the chip
beside the weights. Sizes are read under the names the published
``config.json`` gives them; of the two layouts the first
``num_hidden_layers`` entries are the layers kept.

``lowp=True`` is the lower-precision control: every matmul operand is rounded
to float8_e4m3fn, scaled per row, before a float32 product, the router's and
the attention's among them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512


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


def _rope(x, theta):
    """``x [T, heads, D]`` at positions ``0..T-1``: entry ``i`` turns with
    entry ``i + D/2`` by the angle ``t * theta^(-2i/D)``."""
    t, _, d = x.shape
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window, lowp):
    """``q [T, H, D]``, ``k, v [T, Hkv, D]``; causal, and where ``window`` is
    set position ``t`` sees ``t - window < j <= t``. A block of queries at a
    time against all keys."""
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
        out.append(o.reshape(n, h * d))
    return jnp.concatenate(out, axis=0)


def _experts(p, m, r, cfg, lowp):
    """The routed sum for ``m [T, d]`` under router logits ``r [T, E]``."""
    top, idx = jax.lax.top_k(r, cfg["moe_num_active_primary_experts"])
    w = jax.nn.softmax(top, axis=-1)                         # [T, k]

    def add_expert(y, xs):
        e, w_gate, w_up, w_down = xs
        share = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)
        hidden = jax.nn.relu(_dot(m, w_gate, lowp)) * _dot(m, w_up, lowp)
        return y + share[:, None] * _dot(hidden, w_down, lowp), None

    # one expert at a time (a loop the compiler keeps rolled)
    y, _ = jax.lax.scan(add_expert, jnp.zeros_like(m), (
        jnp.arange(p["w_gate"].shape[0]), p["w_gate"], p["w_up"],
        p["w_down"]))
    return y


def _layer(p, x, i, cfg, lowp):
    """One layer on ``x [T, d]``."""
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    t = x.shape[0]
    r = _dot(x, p["moe"]["router"], lowp)
    a = _rms_norm(x, p["norm_1"], cfg["rms_norm_eps"])
    q = _dot(a, p["q_proj"]["kernel"], lowp).reshape(t, h, dh)
    k = _dot(a, p["k_proj"]["kernel"], lowp).reshape(t, hk, dh)
    v = _dot(a, p["v_proj"]["kernel"], lowp).reshape(t, hk, dh)
    if cfg["rope_layout"][i]:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    window = (cfg["sliding_window_size"]
              if cfg["sliding_window_layout"][i] else None)
    o = _attention(q, k, v, window, lowp)
    x = x + _dot(o, p["o_proj"]["kernel"], lowp)
    m = _rms_norm(x, p["norm_2"], cfg["rms_norm_eps"])
    return x + _experts(p["moe"], m, r, cfg, lowp)


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
    """``[B, T, vocab]`` float32 logits. The head takes the last position
    apart from those before it: a caller that reads ``[:, :-1]`` (every
    position that predicts a token it holds) then reads the first product as
    it stands, where a slice of one product would be a second array of the
    size: 4 GB at 6656 positions of 151,936 logits."""
    x = hidden(params, tokens, cfg, lowp=lowp)
    head = params["params"]["lm_head"]["kernel"]
    return jnp.concatenate([_dot(x[:, :-1], head, lowp),
                            _dot(x[:, -1:], head, lowp)], axis=1)
