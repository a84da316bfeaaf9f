"""Laguna-style decoder: full and sliding-window attention layers with
different numbers of query heads over the same KV heads, a sigmoid gate a
query head on the attention output, partial-rotary YaRN positions on the
full layers and plain RoPE on the window layers, a leading dense layer,
and then a dropless top-k mixture of small SiLU-gated experts with a
shared expert, of which this chip may hold a share.

The layer, as the published ``config.json`` of ``poolside/Laguna-S-2.1``
names its parts (no biases anywhere, embedding and head untied; ``H_i``
is ``heads_per_layer[i]``)::

    a = RMSNorm_1(x_in)
    q, k, v = a @ Wq [H_i, D], a @ Wk [Hkv, D], a @ Wv [Hkv, D]
    g = sigmoid(a @ Wg) [H_i]            # one gate a query head
    q, k = RoPE(q, k)                    # full layers: the first
                                         # rotary_dim entries, YaRN's table,
                                         # cos and sin times its factor;
                                         # window layers: the whole head
    o_h = g_h * softmax(q_h k^T / sqrt(D) over visible) v  # head h reads
                                                           # h // (H_i/Hkv)
    x = x_in + o @ Wo
    m = RMSNorm_2(x)
    y = (silu(m @ Wgate) * (m @ Wup)) @ Wdown              # a dense layer
      = sum_{e in top-k of m @ W_router, e held here}
            routed_scale * softmax(r[top-k])_e * expert_e(m)
        + shared(m)                                        # a sparse layer
    x_out = x + y

``visible(j | t)`` is ``j <= t`` in a full layer and ``t - window < j <=
t`` in a window layer. ``held_experts = (first, count)`` is this chip's
share of an expert-parallel deployment
(:class:`~chainermn_tpu.parallel.moe.DroplessMoE`): the router goes over
all ``n_experts``, what the experts held elsewhere would add is left out,
and that partial stream goes on to the next layer.

Serving is :class:`~chainermn_tpu.models.SmallThinkerLM`'s: two kinds of
KV state (``kv_cache_spec()``), whole fresh prompts or one token a row,
and the same refusals by :class:`~chainermn_tpu.serving.ServingEngine`
for a model with window layers.
"""

from __future__ import annotations

import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from chainermn_tpu.models.smallthinker import (
    attend_through_cache,
    full_and_window_kinds,
    rope,
    rope_inv_freq,
    token_positions,
)
from chainermn_tpu.parallel.moe import DroplessMoE, GatedMLP


def yarn_inv_freq(theta: float, rotary_dim: int, factor: float,
                  original_max_len: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's rotary table (arXiv:2309.00071, as Hugging Face's
    ``_compute_yarn_parameters`` computes it, ``truncate`` on): pairs that
    turn fast keep their plain angle, pairs that turn slowly are
    interpolated by ``factor``, with a linear ramp between the pairs that
    make ``beta_fast`` and ``beta_slow`` turns over the original length.
    ``[rotary_dim / 2]`` float32."""
    half = rotary_dim // 2
    pos_freqs = theta ** (np.arange(half, dtype=np.float64) / half)

    def correction(turns: float) -> float:
        return (rotary_dim * math.log(original_max_len
                                      / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001                      # as the source: no division by 0
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    inv_freq = (1.0 / (factor * pos_freqs)) * ramp + (
        1.0 / pos_freqs) * (1.0 - ramp)
    return inv_freq.astype(np.float32)


class LagunaBlock(nn.Module):
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    window: Optional[int]           # None: a full layer
    positions: tuple                # this layer kind's, see _rope_table
    dense_d_ff: int                 # > 0: a dense layer of that width
    d_ff: int
    n_experts: int
    top_k: int
    held_experts: Optional[tuple]
    routed_scale: float
    shared_d_ff: int
    rms_norm_eps: float
    compute_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x, pos, kv_cache=None):
        dt = self.compute_dtype
        b, s, _ = x.shape
        h, hk, dh = self.n_heads, self.n_kv_heads, self.head_dim
        dense = lambda n, name: nn.Dense(n, use_bias=False, dtype=dt,
                                         name=name)
        a = nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=dt, name="norm_1")(x)
        q = dense(h * dh, "q_proj")(a).reshape(b, s, h, dh)
        k = dense(hk * dh, "k_proj")(a).reshape(b, s, hk, dh)
        v = dense(hk * dh, "v_proj")(a).reshape(b, s, hk, dh)
        gate = jax.nn.sigmoid(dense(h, "g_proj")(a).astype(jnp.float32))
        inv_freq, factor = _rope_table(self.positions, dh)
        q, k = rope(q, k, pos, inv_freq, factor)
        o, new_cache = attend_through_cache(q, k, v, pos, kv_cache,
                                            self.window)
        o = (o.astype(jnp.float32) * gate[..., None]).astype(dt)
        x = x + dense(self.d_model, "o_proj")(o.reshape(b, s, h * dh))
        m = nn.RMSNorm(epsilon=self.rms_norm_eps, dtype=dt, name="norm_2")(x)
        if self.dense_d_ff:
            y = GatedMLP(d_model=self.d_model, d_ff=self.dense_d_ff,
                         compute_dtype=dt, name="mlp")(m)
        else:
            y = DroplessMoE(
                n_experts=self.n_experts, d_model=self.d_model,
                d_ff=self.d_ff, top_k=self.top_k, compute_dtype=dt,
                activation="silu", weight_scale=self.routed_scale,
                held=self.held_experts, shared_d_ff=self.shared_d_ff,
                name="moe")(m)
        return x + y, new_cache


def _rope_table(spec: tuple, head_dim: int):
    """``(inv_freq, factor)`` from a layer kind's positions: ``("default",
    theta, partial_rotary_factor)`` or ``("yarn", theta,
    partial_rotary_factor, factor, original_max_len, beta_fast, beta_slow,
    attention_factor)``."""
    kind, theta, partial = spec[:3]
    rotary_dim = int(head_dim * partial)
    if kind == "default":
        return rope_inv_freq(theta, rotary_dim), 1.0
    if kind != "yarn":
        raise ValueError(f"rope type {kind!r}")
    factor, original, fast, slow, attention_factor = spec[3:]
    return (jnp.asarray(yarn_inv_freq(theta, rotary_dim, factor, original,
                                      fast, slow)), float(attention_factor))


class LagunaLM(nn.Module):
    """``__call__(tokens [B, T], pos_offset)`` -> logits ``[B, T, vocab]``
    in float32; with ``kv_caches`` (one paged cache dict a layer)
    ``(logits, new_caches)``; with ``logits_at [B]`` only those positions
    go through the head, logits ``[B, vocab]``.

    Layer ``i`` has ``heads_per_layer[i]`` query heads, a window where
    ``window_layers[i]``, a dense feed-forward layer of ``dense_d_ff``
    where ``dense_layers[i]`` and the mixture of experts elsewhere.
    ``rope_full`` and ``rope_window`` are the positions of the two layer
    kinds (see ``_rope_table``). ``vocab_size`` is the rows of the
    embedding and the head held here."""

    vocab_size: int
    d_model: int
    n_kv_heads: int
    head_dim: int
    n_layers: int
    heads_per_layer: tuple
    window: int
    window_layers: tuple
    dense_layers: tuple
    dense_d_ff: int
    d_ff: int
    n_experts: int
    top_k: int
    rope_full: tuple
    rope_window: tuple
    held_experts: Optional[tuple] = None
    routed_scale: float = 1.0
    shared_d_ff: int = 0
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
        for name in ("heads_per_layer", "window_layers", "dense_layers"):
            if len(getattr(self, name)) != self.n_layers:
                raise ValueError(f"{name} names every layer")
        dt = self.compute_dtype
        b, t = tokens.shape
        pos = token_positions(pos_offset, b, t)
        x = nn.Embed(self.vocab_size, self.d_model, dtype=dt,
                     name="embed")(tokens)
        new_caches = []
        for i in range(self.n_layers):
            windowed = bool(self.window_layers[i])
            x, c = LagunaBlock(
                d_model=self.d_model, n_heads=self.heads_per_layer[i],
                n_kv_heads=self.n_kv_heads, head_dim=self.head_dim,
                window=self.window if windowed else None,
                positions=(self.rope_window if windowed
                           else self.rope_full),
                dense_d_ff=self.dense_d_ff if self.dense_layers[i] else 0,
                d_ff=self.d_ff, n_experts=self.n_experts, top_k=self.top_k,
                held_experts=self.held_experts,
                routed_scale=self.routed_scale,
                shared_d_ff=self.shared_d_ff,
                rms_norm_eps=self.rms_norm_eps, compute_dtype=dt,
                name=f"block_{i}",
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


__all__ = ["LagunaBlock", "LagunaLM", "yarn_inv_freq"]
