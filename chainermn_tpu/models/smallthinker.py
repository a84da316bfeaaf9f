"""SmallThinker-style decoder: window and full attention layers mixed,
grouped KV heads, and a dropless top-k mixture of small ReLU-gated experts
whose router reads the layer's input.

The layer, as the published ``config.json`` of
``PowerInfer/SmallThinker-21BA3B-Instruct`` names its parts (no biases
anywhere, embedding and head untied)::

    r = x_in @ W_router                  # the router reads the layer's input
    a = RMSNorm_1(x_in)
    q, k, v = a @ Wq [H, D], a @ Wk [Hkv, D], a @ Wv [Hkv, D]
    q, k = RoPE(q, k)                    # where rope_layout[i] == 1 only
    o = softmax(q k^T / sqrt(D) over visible) v     # head g reads g // G
    x = x_in + o @ Wo
    y = sum_{e in top-k of r} softmax(r[top-k])_e * expert_e(RMSNorm_2(x))
    x_out = x + y

``visible(j | t)`` is ``j <= t`` in a full layer and ``t - window < j <=
t`` in a window layer. Layers whose ``rope_layout`` entry is 0 carry no
position encoding at all.

Serving: ``kv_cache_spec()`` tells the engine the two kinds of KV state
(full layers keep every token, window layers at most ``window`` and a
block). With ``kv_caches`` the model takes either whole fresh prompts
from position 0 (``S > 1``: each layer attends its own K/V with the flash
kernel, grouped and windowed, and writes the store beside it) or one
token a row (``S == 1``: written through the block table, then read back
by the paged kernel or its XLA twin). A prompt continued at an offset
(prefix reuse, chunked prefill, a speculative window) is not among the
shapes; :class:`~chainermn_tpu.serving.ServingEngine` refuses those
options for a model with window layers.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax.numpy as jnp

from chainermn_tpu.models.transformer import KVCacheKind
from chainermn_tpu.parallel.moe import DroplessMoE


def rope_inv_freq(theta: float, rotary_dim: int):
    """The plain rotary table: entry ``j`` of ``rotary_dim / 2`` turns by
    ``theta^(-2j / rotary_dim)`` a position."""
    half = rotary_dim // 2
    return theta ** (-jnp.arange(half, dtype=jnp.float32) / half)


def rope(q, k, pos, inv_freq, factor: float = 1.0):
    """Rotary embedding with rotate-half pairing over the first ``2 *
    len(inv_freq)`` entries of each head (entry ``i`` pairs with ``i +
    len(inv_freq)``; the entries past them pass through): ``q [B, S, H,
    D]``, ``k [B, S, Hkv, D]``, ``pos [B, S]``, ``inv_freq`` the angle each
    pair turns by a position, ``factor`` what cos and sin are multiplied
    by (YaRN's attention factor). Angles in float32."""
    half = inv_freq.shape[0]
    ang = pos.astype(jnp.float32)[..., None] * inv_freq       # [B, S, half]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, :, None]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, :, None]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor

    def turn(x):
        partial = 2 * half < x.shape[-1]
        x32 = (x[..., :2 * half] if partial else x).astype(jnp.float32)
        rot = jnp.concatenate([-x32[..., half:], x32[..., :half]], -1)
        out = (x32 * cos + rot * sin).astype(x.dtype)
        if partial:
            out = jnp.concatenate([out, x[..., 2 * half:]], -1)
        return out

    return turn(q), turn(k)


def attend_through_cache(q, k, v, pos, kv_cache, window: Optional[int]):
    """Causal attention of ``q [B, S, H, D]`` over ``k, v [B, S, Hkv, D]``
    as a served decoder block runs it, ``(o [B, S, H, D], new_cache)``:
    one token a row (``S == 1`` with a cache) is written through the block
    table and read back by the paged kernel or its XLA twin; whole fresh
    prompts from position 0 attend their own K/V with the flash kernel,
    grouped and windowed, and are written to the store beside it where
    there is one."""
    from chainermn_tpu.ops import flash_attention
    from chainermn_tpu.parallel.sequence import (
        paged_update_cache_and_attend,
        paged_write_kv,
    )

    if kv_cache is not None and q.shape[1] == 1:
        return paged_update_cache_and_attend(kv_cache, q, k, v, pos[:, 0])
    new_cache = None
    if kv_cache is not None:
        new_cache = paged_write_kv(kv_cache, k, v, pos[:, 0])
    return flash_attention(q, k, v, causal=True, window=window), new_cache


def full_and_window_kinds(window_layers, window: int, kv_heads: int,
                          head_dim: int) -> tuple:
    """What a model of full and window layers tells the serving engine
    (``kv_cache_spec()``): two kinds, full layers keep every token, window
    layers the ``window`` positions a query sees (the engine adds a block,
    for the one being written). A layer's query heads are not the store's
    business."""
    kinds = []
    for name, flag, span in (("full", False, None), ("window", True, window)):
        layers = tuple(i for i, w in enumerate(window_layers)
                       if bool(w) == flag)
        if layers:
            kinds.append(KVCacheKind(name, layers, kv_heads, head_dim, span))
    return tuple(kinds)


def token_positions(pos_offset, b: int, t: int):
    """``[b, t]`` positions from what a model's ``pos_offset`` may be: a
    scalar first position, a row ``[t]``, or the positions themselves."""
    if jnp.ndim(pos_offset) == 2:
        return pos_offset
    return jnp.broadcast_to(
        (pos_offset + jnp.arange(t)) if jnp.ndim(pos_offset) == 0
        else pos_offset, (b, t))


class SmallThinkerBlock(nn.Module):
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    n_experts: int
    top_k: int
    window: Optional[int]           # None: a full layer
    use_rope: bool
    rope_theta: float
    rms_norm_eps: float
    compute_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, pos, kv_cache=None):
        dt = self.compute_dtype
        b, s, _ = x.shape
        h, hk, dh = self.n_heads, self.n_kv_heads, self.head_dim
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=dt,
                                         name=name)
        x_in = x
        a = nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=dt, name="norm_1")(x)
        q = dense(h * dh, "q_proj")(a).reshape(b, s, h, dh)
        k = dense(hk * dh, "k_proj")(a).reshape(b, s, hk, dh)
        v = dense(hk * dh, "v_proj")(a).reshape(b, s, hk, dh)
        if self.use_rope:
            q, k = rope(q, k, pos, rope_inv_freq(self.rope_theta, dh))
        o, new_cache = attend_through_cache(q, k, v, pos, kv_cache,
                                            self.window)
        x = x + dense(self.d_model, "o_proj")(o.reshape(b, s, h * dh))
        m = nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=dt, name="norm_2")(x)
        y = DroplessMoE(
            n_experts=self.n_experts, d_model=self.d_model, d_ff=self.d_ff,
            top_k=self.top_k, compute_dtype=dt, name="moe",
        )(m, router_in=x_in)
        return x + y, new_cache


class SmallThinkerLM(nn.Module):
    """``__call__(tokens [B, T], pos_offset)`` -> logits ``[B, T, vocab]``
    in float32; with ``kv_caches`` (one paged cache dict a layer, see the
    module docstring) ``(logits, new_caches)``; with ``logits_at [B]`` only
    those positions go through the head, logits ``[B, vocab]``.

    ``window_layers[i]`` and ``rope_layers[i]`` are the published
    ``sliding_window_layout`` and ``rope_layout`` for the layers kept."""

    vocab_size: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    n_layers: int
    d_ff: int
    n_experts: int
    top_k: int
    window: int
    window_layers: tuple
    rope_layers: tuple
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    max_len: int = 16384
    compute_dtype: jnp.dtype = jnp.bfloat16
    # what ServingEngine asks of any model it serves; neither is offered
    sequence_axis: Optional[str] = None
    tensor_axis: Optional[str] = None

    def kv_cache_spec(self) -> tuple:
        return full_and_window_kinds(self.window_layers[:self.n_layers],
                                     self.window, self.n_kv_heads,
                                     self.head_dim)

    @nn.compact
    def __call__(self, tokens, pos_offset=0, kv_caches=None, logits_at=None):
        if len(self.window_layers) != self.n_layers or len(
                self.rope_layers) != self.n_layers:
            raise ValueError("window_layers and rope_layers name every layer")
        dt = self.compute_dtype
        b, t = tokens.shape
        pos = token_positions(pos_offset, b, t)
        x = nn.Embed(self.vocab_size, self.d_model, dtype=dt,
                     name="embed")(tokens)
        new_caches = []
        for i in range(self.n_layers):
            x, c = SmallThinkerBlock(
                d_model=self.d_model, n_heads=self.n_heads,
                n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
                d_ff=self.d_ff, n_experts=self.n_experts, top_k=self.top_k,
                window=self.window if self.window_layers[i] else None,
                use_rope=bool(self.rope_layers[i]),
                rope_theta=self.rope_theta, rms_norm_eps=self.rms_norm_eps,
                compute_dtype=dt, name=f"block_{i}",
            )(x, pos, None if kv_caches is None else kv_caches[i])
            new_caches.append(c)
        if logits_at is not None:
            x = jnp.take_along_axis(
                x, logits_at[:, None, None], axis=1)[:, 0]
        x = nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=dt, name="norm")(x)
        logits = nn.Dense(self.vocab_size, use_bias=False, dtype=dt,
                          name="lm_head")(x).astype(jnp.float32)
        if kv_caches is not None:
            return logits, new_caches
        return logits


__all__ = ["SmallThinkerBlock", "SmallThinkerLM", "attend_through_cache",
           "full_and_window_kinds", "rope", "rope_inv_freq", "token_positions"]
