"""Memory-lean losses: token-chunked softmax cross entropy.

The reference delegates losses to its host framework (SURVEY.md S0 — no
loss ops of its own); this op exists for the rebuild's long-context LM
flagship, where the LOSS — not the model — sets the memory ceiling: the
``[B*T, vocab]`` f32 logits and their gradient are the two largest
tensors in the whole train step (scripts/lm_roofline_aot.jsonl: at
T=2048 B=32, d=1024, V=32k the pair is ~17 GB — past a 16 GB v5e even
with block remat; full attention at B=8 cannot compile at all).

:func:`chunked_softmax_cross_entropy` fuses the LM head matmul with the
cross entropy under a custom VJP that processes tokens in chunks:

- forward: one ``[chunk, V]`` logits tile at a time -> per-token
  ``lse`` and target logit; the tile dies inside the ``lax.map`` body,
  so live memory is O(chunk * V) instead of O(B*T * V);
- backward: recomputes each tile from the saved ``lse`` (flash
  attention's trick applied to the vocabulary axis), forms
  ``dlogits = (softmax - onehot) * g`` tile-locally, and accumulates
  ``dhidden`` / ``dkernel`` / ``dbias`` in f32 — the full dlogits never
  exists either.

Numerics: matches ``optax.softmax_cross_entropy_with_integer_labels``
on the materialized logits to fp tolerance (pinned in tests, values and
grads); the matmul accumulates in f32 via ``preferred_element_type``
from storage-dtype operands, the same contract as the flash kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_DEFAULT_CHUNK = 4096


def _pad_to_multiple(x, n, axis=0, value=0):
    pad = (-x.shape[axis]) % n
    if pad == 0:
        return x, 0
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value), pad


def _tile_logits(h_c, kernel, bias):
    """One chunk's f32 logits tile from storage-dtype operands."""
    lg = jax.lax.dot_general(
        h_c, kernel, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    if bias is not None:
        lg = lg + bias.astype(jnp.float32)
    return lg


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _chunked_ce(hidden, kernel, bias, targets, chunk):
    losses, _ = _ce_fwd_core(hidden, kernel, bias, targets, chunk)
    return losses


def _ce_fwd_core(hidden, kernel, bias, targets, chunk):
    n = hidden.shape[0]
    h_p, _ = _pad_to_multiple(hidden, chunk)
    t_p, _ = _pad_to_multiple(targets, chunk)
    n_chunks = h_p.shape[0] // chunk
    h_c = h_p.reshape(n_chunks, chunk, hidden.shape[1])
    t_c = t_p.reshape(n_chunks, chunk)

    def body(args):
        h_i, t_i = args
        lg = _tile_logits(h_i, kernel, bias)
        m = jnp.max(lg, axis=-1)
        lse = m + jnp.log(jnp.sum(jnp.exp(lg - m[:, None]), axis=-1))
        t_logit = jnp.take_along_axis(lg, t_i[:, None], axis=-1)[:, 0]
        return lse - t_logit, lse

    losses, lse = jax.lax.map(body, (h_c, t_c))
    return losses.reshape(-1)[:n], lse.reshape(-1)[:n]


def _ce_fwd(hidden, kernel, bias, targets, chunk):
    losses, lse = _ce_fwd_core(hidden, kernel, bias, targets, chunk)
    return losses, (hidden, kernel, bias, targets, lse)


def _ce_bwd(chunk, res, g):
    hidden, kernel, bias, targets, lse = res
    n, d = hidden.shape
    v = kernel.shape[1]
    h_p, _ = _pad_to_multiple(hidden, chunk)
    t_p, _ = _pad_to_multiple(targets, chunk)
    # a pad row's logits are the bias alone: with lse = +inf its p is
    # exactly 0, where lse = 0 gave exp(bias), inf past ~88, and inf * 0
    lse_p, _ = _pad_to_multiple(lse, chunk, value=jnp.inf)
    # padded tokens carry zero cotangent -> contribute nothing anywhere
    g_p, _ = _pad_to_multiple(g.astype(jnp.float32), chunk)
    n_chunks = h_p.shape[0] // chunk
    h_c = h_p.reshape(n_chunks, chunk, d)
    t_c = t_p.reshape(n_chunks, chunk)
    lse_c = lse_p.reshape(n_chunks, chunk)
    g_c = g_p.reshape(n_chunks, chunk)

    def body(carry, args):
        dk_acc, db_acc = carry
        h_i, t_i, lse_i, g_i = args
        lg = _tile_logits(h_i, kernel, bias)
        p = jnp.exp(lg - lse_i[:, None])
        onehot = jax.nn.one_hot(t_i, v, dtype=jnp.float32)
        dlg = (p - onehot) * g_i[:, None]
        dh_i = jax.lax.dot_general(
            dlg.astype(kernel.dtype), kernel, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc = dk_acc + jax.lax.dot_general(
            h_i, dlg.astype(h_i.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        db_acc = db_acc + jnp.sum(dlg, axis=0)
        return (dk_acc, db_acc), dh_i

    # the zero init must carry the same varying-manner annotation as the
    # per-chunk updates or lax.scan rejects the carry under shard_map
    # (the train step pcasts params to varying); adding a data-derived
    # zero scalar transfers the vma without knowing the axes
    vma_zero = (g_c.ravel()[0] * 0.0 + h_c.ravel()[0].astype(jnp.float32)
                * 0.0)
    (dk, db), dh = jax.lax.scan(
        body,
        (jnp.zeros((d, v), jnp.float32) + vma_zero,
         jnp.zeros((v,), jnp.float32) + vma_zero),
        (h_c, t_c, lse_c, g_c))
    dh = dh.reshape(-1, d)[:n].astype(hidden.dtype)
    dbias = None if bias is None else db.astype(bias.dtype)
    return dh, dk.astype(kernel.dtype), dbias, None


_chunked_ce.defvjp(_ce_fwd, _ce_bwd)


def chunked_softmax_cross_entropy(hidden, kernel, bias, targets, *,
                                  chunk_size: int = _DEFAULT_CHUNK):
    """Per-token cross entropy of ``softmax(hidden @ kernel + bias)``
    against integer ``targets`` without materializing the logits.

    Args:
      hidden: ``[..., d]`` final hidden states (any float dtype; the
        logits tile accumulates in f32 from the storage dtype).
      kernel: ``[d, vocab]`` LM head weight (the flax ``Dense`` kernel).
      bias: ``[vocab]`` or None.
      targets: ``[...]`` integer ids, same leading shape as ``hidden``.
      chunk_size: tokens per logits tile; live memory is
        O(chunk_size * vocab) f32. The default (4096) costs a 0.5 GB
        tile at vocab 32k.

    Returns per-token f32 losses shaped like ``targets`` (the same
    contract as ``optax.softmax_cross_entropy_with_integer_labels``).
    Differentiable wrt hidden/kernel/bias via the chunked custom VJP.
    """
    lead = targets.shape
    d = hidden.shape[-1]
    losses = _chunked_ce(hidden.reshape(-1, d), kernel, bias,
                         targets.reshape(-1), int(chunk_size))
    return losses.reshape(lead)
