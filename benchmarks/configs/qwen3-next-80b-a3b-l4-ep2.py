"""Plain reference for the Qwen3-Next layer as
``qwen3-next-80b-a3b-l4-ep2.json`` states it, one chip's share of the routed
experts included.

Straight ``jax.numpy`` in float32 with ``precision=highest``: no kernels, no
cache, no chunks, no sorting. It imports nothing of the program and is given
weights the benchmark made. Layer ``i`` is a linear-attention layer where
``(i + 1) % full_attention_interval`` is not 0 and a full-attention layer
where it is; ``x_in [T, d]`` is the residual stream entering it::

    a = RMSNorm_1(x_in)

    linear-attention layer (Gated DeltaNet, arXiv:2412.06464):
      Hk, Hv, dk, dv, K = linear_num_key_heads, linear_num_value_heads,
                          linear_key_head_dim, linear_value_head_dim,
                          linear_conv_kernel_dim
      q, k [T, Hk, dk], v, z [T, Hv, dv] = a @ W_qkvz ;  b, al [T, Hv] = a @ W_ba
      u = concat(q, k, v)
      c_t = silu(sum_{j<K} w_conv[j] * u_{t-K+1+j})     causal, depthwise,
                                                        u_{<0} = 0, no bias
      q, k, v = split(c)
      beta_t = sigmoid(b_t) ;  g_t = -exp(A_log) * softplus(al_t + dt_bias)
      q = q / sqrt(sum q^2 + 1e-6) / sqrt(dk) ;  k = k / sqrt(sum k^2 + 1e-6)
      value head h reads key head h // (Hv / Hk)
      per value head, S [dk, dv], S_{-1} = 0, a token at a time:
          S   = exp(g_t) * S_{t-1}
          d_t = beta_t * (v_t - S^T k_t)
          S_t = S + k_t d_t^T
          o_t = S_t^T q_t
      y_t,h = o_t,h * rsqrt(mean o_t,h^2 + eps) * w_norm * silu(z_t,h)
      x = x_in + concat_h(y) @ W_out

    full-attention layer:
      (q, gate) [T, H, D] each = a @ W_q ;  k, v [T, Hkv, D] = a @ W_k, a @ W_v
      q = RMSNorm_D(q), k = RMSNorm_D(k)                per head, before RoPE
      RoPE on entries 0..R-1 of each head, R = D * partial_rotary_factor,
      entry j pairing with j + R/2, rope_theta, no scaling; the rest pass
      o_h = softmax(q_h k^T / sqrt(D) over j <= t) v    head h reads KV head
                                                        h // (H / Hkv)
      x = x_in + (concat_h(o_h) * sigmoid(gate)) @ W_o

    m = RMSNorm_2(x)
    r = m @ W_router [num_experts published] ;  p = softmax(r)
    top = the num_experts_per_tok largest ;  w_e = p_e / sum_{top} p
    y = sum_{e in top, first <= e < first + count} w_e * expert_e(m)
        + sigmoid(m @ w_sg) * shared(m)
    (experts and shared: (silu(. @ Wgate) * (. @ Wup)) @ Wdown)
    x_out = x + y

then a final RMSNorm and an untied head over the vocabulary rows held; no
bias anywhere. The norms' leaf ``scale`` holds the published ``1 + w`` (the
gated norm inside the linear layer holds its plain ``w``). ``W_qkvz``'s
columns are q, k, v, z each in one run (``W_ba``'s b then a, ``W_q``'s
queries then gates), where the published weights interleave them by head: a
permutation of the columns of a random matrix. ``held_experts`` (``first``,
``count``) is the share of the routed experts whose weights are here: the
router still goes over all the published experts, what an expert held
elsewhere would add is left out, and that partial stream goes on. Attention
runs over blocks of queries and the experts one at a time in a ``lax.scan``,
so that a sequence of the cell's ``cache_len`` fits on the chip beside the
weights.

``lowp=True`` is the lower-precision control: every product's operands are
rounded to float8_e4m3fn, scaled per row, before a float32 product: the
matmuls', the router's, the attention's, the convolution's and the
recurrence's (q, k, v as they enter it and the state where it is read).
"""

from __future__ import annotations

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


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                        + 1e-6)


def _rope(x, cfg):
    """``x [T, heads, D]`` at positions ``0..T-1``: the first
    ``D * partial_rotary_factor`` entries of each head turn."""
    t, _, d = x.shape
    dim = int(d * cfg["partial_rotary_factor"])
    half = dim // 2
    inv_freq = float(cfg["rope_theta"]) ** (
        -2.0 * jnp.arange(half, dtype=jnp.float32) / dim)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:dim], x[..., dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _attention(q, k, v, lowp):
    """``q [T, H, D]``, ``k, v [T, Hkv, D]``, causal. A block of queries at
    a time against all keys; ``[T, H * D]``."""
    t, h, d = q.shape
    g = h // k.shape[1]
    if lowp:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    j = jnp.arange(t)
    out = []
    for lo in range(0, t, QUERY_BLOCK):
        qb = q[lo:lo + QUERY_BLOCK]
        n = qb.shape[0]
        qb = qb.reshape(n, k.shape[1], g, d)
        s = jnp.einsum("qhgd,khd->hgqk", qb, k, precision=HIGHEST)
        s = s / jnp.sqrt(jnp.float32(d))
        i = (lo + jnp.arange(n))[:, None]
        s = jnp.where((j[None, :] <= i)[None, None], s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", w, v, precision=HIGHEST)
        out.append(o.reshape(n, h * d))
    return jnp.concatenate(out, axis=0)


def _full_attention(p, a, cfg, lowp):
    h, hk, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    t = a.shape[0]
    qg = _dot(a, p["q_proj"]["kernel"], lowp)
    q, gate = qg[:, :h * dh].reshape(t, h, dh), qg[:, h * dh:]
    k = _dot(a, p["k_proj"]["kernel"], lowp).reshape(t, hk, dh)
    v = _dot(a, p["v_proj"]["kernel"], lowp).reshape(t, hk, dh)
    q = _rms_norm(q, p["q_norm"], cfg["rms_norm_eps"])
    k = _rms_norm(k, p["k_norm"], cfg["rms_norm_eps"])
    o = _attention(_rope(q, cfg), _rope(k, cfg), v, lowp)
    return _dot(o * jax.nn.sigmoid(gate), p["o_proj"]["kernel"], lowp)


def _delta_rule(q, k, v, g, beta, lowp):
    """The recurrence, a token at a time: ``q, k [T, Hv, dk]``, ``v [T, Hv,
    dv]``, ``g, beta [T, Hv]``; ``o [T, Hv, dv]``."""
    if lowp:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)

    def step(s, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        s = s * jnp.exp(g_t)[:, None, None]
        read = _fp8(s) if lowp else s        # as the products read it
        d_t = beta_t[:, None] * (v_t - jnp.sum(read * k_t[:, :, None], 1))
        s = s + k_t[:, :, None] * d_t[:, None, :]
        read = _fp8(s) if lowp else s
        return s, jnp.sum(read * q_t[:, :, None], 1)

    hv, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    _, o = jax.lax.scan(step, jnp.zeros((hv, dk, dv), jnp.float32),
                        (q, k, v, g, beta))
    return o


def _gated_delta_net(p, a, cfg, lowp):
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    kk = cfg["linear_conv_kernel_dim"]
    t = a.shape[0]
    key_dim, value_dim = hk * dk, hv * dv
    c = 2 * key_dim + value_dim
    qkvz = _dot(a, p["qkvz_proj"]["kernel"], lowp)
    ba = _dot(a, p["ba_proj"]["kernel"], lowp)
    u, z = qkvz[:, :c], qkvz[:, c:]
    w = p["conv_kernel"].astype(jnp.float32)                  # [K, C]
    if lowp:
        u, w = _fp8(u), _fp8(w)
    padded = jnp.concatenate([jnp.zeros((kk - 1, c), jnp.float32), u], 0)
    mixed = jax.nn.silu(sum(w[j] * padded[j:j + t] for j in range(kk)))
    q = mixed[:, :key_dim].reshape(t, hk, dk)
    k = mixed[:, key_dim:2 * key_dim].reshape(t, hk, dk)
    v = mixed[:, 2 * key_dim:].reshape(t, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[:, hv:] + p["dt_bias"].astype(jnp.float32))
    q = _l2norm(q) / jnp.sqrt(jnp.float32(dk))
    k = _l2norm(k)
    q, k = (jnp.repeat(x, hv // hk, axis=1) for x in (q, k))
    o = _delta_rule(q, k, v, g, beta, lowp)                   # [T, Hv, dv]
    y = _rms_norm(o, p["norm"], cfg["rms_norm_eps"]) * jax.nn.silu(
        z.reshape(t, hv, dv))
    return _dot(y.reshape(t, value_dim), p["out_proj"]["kernel"], lowp)


def _gated(x, w_gate, w_up, w_down, lowp):
    hidden = jax.nn.silu(_dot(x, w_gate, lowp)) * _dot(x, w_up, lowp)
    return _dot(hidden, w_down, lowp)


def _experts(p, m, cfg, lowp):
    """The routed sum over the experts held, for ``m [T, d]``."""
    first = cfg["held_experts"]["first"]
    prob = jax.nn.softmax(_dot(m, p["router"], lowp), axis=-1)
    top, idx = jax.lax.top_k(prob, cfg["num_experts_per_tok"])
    w = top / jnp.sum(top, axis=-1, keepdims=True)

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
    a = _rms_norm(x, p["norm_1"], cfg["rms_norm_eps"])
    if (i + 1) % cfg["full_attention_interval"]:
        x = x + _gated_delta_net(p["gdn"], a, cfg, lowp)
    else:
        x = x + _full_attention(p["attn"], a, cfg, lowp)
    m = _rms_norm(x, p["norm_2"], cfg["rms_norm_eps"])
    moe = p["moe"]
    shared = moe["shared"]
    gate = jax.nn.sigmoid(_dot(m, moe["shared_gate"], lowp))
    y = _experts(moe, m, cfg, lowp) + gate * _gated(
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
