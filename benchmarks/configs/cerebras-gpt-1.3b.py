"""Plain reference for the GPT-2 block as ``cerebras-gpt-1.3b.json`` states it.

Straight ``jax.numpy`` in float32 with ``precision=highest``: no kernels, no
cache, no batching tricks. It imports nothing of the program and is given
weights the benchmark made. Decoder-only, pre-LayerNorm, learned positions,
biased projections, tanh GELU, an untied biased head (the departures from the
published model are listed in the configuration's file).

``lowp=True`` is the lower-precision control: every matmul operand is rounded
to float8_e4m3fn, scaled per row, before a float32 product. It is what a later
PR would be tempted to do to a bfloat16 path, and the comparison that decides
``correct`` has to tell it from the stated precision.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _fp8(x):
    """Round to float8_e4m3fn and back, with one scale per row (last axis)."""
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    r = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    # rounding has no useful derivative: gradients pass straight through
    return x + jax.lax.stop_gradient(r - x)


def _dot(x, w, lowp, n_contract=1):
    """``x [..., *c] @ w [*c, *out]`` over ``n_contract`` axes, in float32."""
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    cx = tuple(range(x.ndim - n_contract, x.ndim))
    cw = tuple(range(n_contract))
    if lowp:
        shape = x.shape
        x = _fp8(x.reshape(shape[:x.ndim - n_contract] + (-1,))).reshape(shape)
        wf = w.reshape((-1,) + w.shape[n_contract:])
        # one scale per output column: rows of the transposed weight
        wf = jnp.moveaxis(_fp8(jnp.moveaxis(wf, 0, -1)), -1, 0)
        w = wf.reshape(w.shape)
    return jax.lax.dot_general(x, w, ((cx, cw), ((), ())), precision=HIGHEST)


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    return y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _block(p, x, cfg, lowp):
    """One pre-LN block on ``x [B, T, d]``; full causal attention."""
    eps = cfg["layer_norm_epsilon"]
    n_heads = cfg["n_heads"]
    d_head = cfg["d_model"] // n_heads
    b, t, _ = x.shape
    h = _layer_norm(x, p["LayerNorm_0"], eps)
    qkv = _dot(h, p["qkv"]["kernel"], lowp) + p["qkv"]["bias"].astype(
        jnp.float32)                                   # [B, T, 3, H, Dh]
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    if lowp:
        q, k, v = _fp8(q), _fp8(k), _fp8(v)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
    s = s / jnp.sqrt(jnp.float32(d_head))
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HIGHEST)
    x = x + _dot(o, p["proj"]["kernel"], lowp, n_contract=2) + p["proj"][
        "bias"].astype(jnp.float32)
    h = _layer_norm(x, p["LayerNorm_1"], eps)
    h = _dot(h, p["Dense_0"]["kernel"], lowp) + p["Dense_0"]["bias"].astype(
        jnp.float32)
    h = _gelu_tanh(h)
    return x + _dot(h, p["Dense_1"]["kernel"], lowp) + p["Dense_1"][
        "bias"].astype(jnp.float32)


def hidden(params, tokens, cfg, lowp=False, remat=False):
    """Final-norm hidden states ``[B, T, d]`` for ``tokens [B, T]`` at
    positions ``0..T-1``."""
    p = params["params"]
    t = tokens.shape[1]
    x = p["embed"]["embedding"].astype(jnp.float32)[tokens]
    x = x + p["pos_embed"]["embedding"].astype(jnp.float32)[:t][None]
    block = functools.partial(_block, cfg=cfg, lowp=lowp)
    if remat:
        block = jax.checkpoint(block)
    for i in range(cfg["n_layers"]):
        x = block(p[f"block_{i}"], x)
    return _layer_norm(x, p["LayerNorm_0"], cfg["layer_norm_epsilon"])


def logits(params, tokens, cfg, lowp=False, remat=False):
    """``[B, T, vocab]`` float32 logits."""
    head = params["params"]["lm_head"]
    x = hidden(params, tokens, cfg, lowp=lowp, remat=remat)
    return _dot(x, head["kernel"], lowp) + head["bias"].astype(jnp.float32)


def loss(params, tokens, targets, cfg, lowp=False):
    """Mean next-token cross-entropy over every position of the rows given."""
    lg = logits(params, tokens, cfg, lowp=lowp, remat=True)
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - picked)
