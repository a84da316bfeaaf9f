"""Speculative decode for the paged serving engine: draft k tokens
cheaply, verify them in ONE target-model call, commit the accepted run.

``ServingEngine.decode_step`` advances every slot exactly one token per
device dispatch, so generation pays the per-dispatch overhead once per
token (PERF.md's dispatch-bound regime) and reads the whole KV working
set once per token (the bandwidth-bound regime). Speculative decode
attacks both at once: a cheap *drafter* proposes ``k`` continuation
tokens per slot, the target model scores the window ``[t0, d1..dk]`` at
positions ``[p..p+k]`` in ONE compiled call, and the engine commits the
longest prefix of drafts that match the target's own greedy choices plus
one correction token — between 1 and ``k+1`` tokens per dispatch, always
at least the one token the plain path would have produced.

Greedy only, and exactly: the verify program recomputes the target's
argmax at every drafted position, so the committed stream is
token-for-token identical to non-speculative greedy decode regardless of
what the drafter proposed (a bad drafter costs speed, never
correctness). That parity argument is causal induction: logits at window
row ``j`` depend only on committed tokens plus drafts ``d1..dj``, and a
row's output is only committed when every draft before it matched.

Two drafters ship behind one interface (:class:`SpeculativeConfig`):

- ``'ngram'`` — :class:`NgramDrafter`, model-free prompt-lookup
  decoding (PLD): the longest trailing n-gram of the request's own
  history (prompt + generated) that occurred earlier proposes the
  tokens that followed it, falling back to the shared prefix trie
  (:meth:`~chainermn_tpu.serving.prefix_cache.PrefixCacheIndex.
  ngram_continuation`) and finally to repeating the last token. Zero
  extra weights, zero extra device programs — strongest on repetitive
  and shared-system-prompt workloads.
- ``'draft'`` — :class:`DraftModelDrafter`, a small ``TransformerLM``
  decoding ``k`` greedy tokens per window against its own dense slot
  caches (two extra compiled programs: one full-prompt prefill, one
  all-slots decode step). The draft caches stay consistent across
  partial acceptance by the same write-before-attend argument the
  engine's slot reuse rides on: every propose window rewrites the rows
  a rejected draft left behind before any query attends them.

The engine side (verify program, block-table scatter of up to ``k+1``
rows per slot, per-slot accept mask, position bookkeeping, block
rollback) lives in ``engine.py``; this module is the drafter state
machine plus its host/device programs.

Under ``ServingEngine(paged_kernel=True)`` the verify window's
attention reads ride the fused Pallas paged-decode kernel
(``parallel.paged_kernel.paged_attend``) like every other decode shape:
the S=k+1 window is ``k+1`` times the query rows against the same chunks
of each slot's live blocks (the sweep's trip count comes from
``lengths = pos + S``), and the per-slot ``valid`` write caps redirect
rejected rows before the kernel ever reads them, so acceptance
bookkeeping is unchanged and the committed
stream stays token-for-token identical to the XLA paged path (pinned
in ``tests/serving_tests/test_paged_kernel_engine.py``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

__all__ = [
    "DraftModelDrafter",
    "NgramDrafter",
    "SpeculativeConfig",
    "build_drafter",
]


@dataclass
class SpeculativeConfig:
    """Speculative-decode configuration for ``ServingEngine(speculative=)``.

    Parameters
    ----------
    k : int
        Drafted tokens per verify window. Each decode dispatch scores
        ``k + 1`` positions and commits ``1..k+1`` tokens; the block
        budget reserves ``ceil(k / kv_block_size)`` extra headroom per
        slot for the window's worst-case writes.
    drafter : {'ngram', 'draft'}
        ``'ngram'``: model-free prompt-lookup drafting from the
        request's own history and the shared prefix trie.
        ``'draft'``: a small ``TransformerLM`` draft model
        (``draft_model`` + ``draft_params`` required).
    draft_model / draft_params : the draft ``TransformerLM`` and its
        params (``drafter='draft'`` only). Must share the target's
        vocabulary, must not be tensor/sequence-sharded, and needs
        ``max_len >= cache_len``.
    ngram_max / ngram_min : longest/shortest trailing n-gram the
        prompt-lookup drafter tries to match (longest first).
    """

    k: int = 4
    drafter: str = "ngram"
    draft_model: object = None
    draft_params: object = None
    ngram_max: int = 3
    ngram_min: int = 1

    def validate(self) -> None:
        if self.k < 1:
            raise ValueError(f"speculative k must be >= 1, got {self.k}")
        if self.drafter not in ("ngram", "draft"):
            raise ValueError(
                f"drafter must be 'ngram' or 'draft', got {self.drafter!r}")
        if not 1 <= self.ngram_min <= self.ngram_max:
            raise ValueError(
                f"need 1 <= ngram_min <= ngram_max, got "
                f"({self.ngram_min}, {self.ngram_max})")
        if self.drafter == "draft" and (
                self.draft_model is None or self.draft_params is None):
            raise ValueError(
                "drafter='draft' needs draft_model= and draft_params=")


class NgramDrafter:
    """Model-free prompt-lookup drafter (PLD / lookahead-by-lookup).

    Per-slot host state only: the request's token history (prompt +
    committed tokens). ``propose`` finds the most recent earlier
    occurrence of the history's trailing n-gram (longest n first) and
    proposes the tokens that followed it; on a miss it probes the shared
    prefix trie (another request's cached prompt may extend ours), and
    as a last resort repeats the last committed token — which is the
    *optimal* draft whenever greedy decode has entered a fixed point.
    Wrong proposals cost nothing but speed: the verify step rejects
    them. No device programs, nothing to warm up or guard."""

    def __init__(self, config: SpeculativeConfig, engine) -> None:
        self.config = config
        self.engine = engine
        self._hist: list[list[int]] = [[] for _ in range(engine.n_slots)]

    # -- slot lifecycle (engine-driven) -------------------------------- #

    def on_admit(self, slot: int, prompt, first_token: int) -> None:
        self._hist[slot] = [int(t) for t in prompt] + [int(first_token)]

    def on_commit(self, slot: int, tokens) -> None:
        self._hist[slot].extend(int(t) for t in tokens)

    def on_release(self, slot: int) -> None:
        self._hist[slot] = []

    def reset(self) -> None:
        self._hist = [[] for _ in range(self.engine.n_slots)]

    # -- drafting ------------------------------------------------------- #

    def _lookup(self, hist: list[int], k: int) -> list[int]:
        """Most recent earlier occurrence of the trailing n-gram, longest
        n first; the tokens following it are the draft."""
        h = np.asarray(hist, np.int32)
        length = len(h)
        hi = min(self.config.ngram_max, length - 1)
        for n in range(hi, self.config.ngram_min - 1, -1):
            tail = h[length - n:]
            win = np.lib.stride_tricks.sliding_window_view(h, n)
            # windows starting before the tail itself (index < length-n)
            hits = np.flatnonzero((win[: length - n] == tail).all(axis=1))
            if hits.size:
                i = int(hits[-1])
                cont = h[i + n: i + n + k]
                if cont.size:
                    return [int(t) for t in cont]
        return []

    def propose(self, k: int) -> np.ndarray:
        """``[n_slots, k]`` int32 draft tokens; inactive slots are zeros
        (the verify program masks them anyway)."""
        eng = self.engine
        out = np.zeros((eng.n_slots, k), np.int32)
        trie = eng.prefix_cache
        for slot in np.flatnonzero(eng._active):
            slot = int(slot)
            hist = self._hist[slot]
            if not hist:
                # admitted outside the scheduler path (direct engine
                # use): behave as if history were just the last token
                hist = [int(eng._token[slot])]
            draft = self._lookup(hist, k)
            if len(draft) < k and trie is not None:
                cont = trie.ngram_continuation(hist + draft,
                                               k - len(draft))
                if cont:
                    draft.extend(cont)
            last = draft[-1] if draft else hist[-1]
            while len(draft) < k:
                draft.append(int(last))
            out[slot, :] = draft[:k]
        return out

    # -- engine integration stubs (no device programs) ------------------ #

    def warmup(self) -> None:
        pass

    def watched_fns(self) -> dict:
        return {}

    def compile_counts(self) -> dict:
        return {}


class DraftModelDrafter:
    """Small-``TransformerLM`` drafter: dense per-slot KV caches plus two
    compiled programs (a single-request full-prompt prefill and an
    all-slots one-token decode), both greedy-argmax — draft tokens are
    *proposals*, so the drafter never needs the engine's sampler keys.

    Cache consistency across partial acceptance: a propose window at
    base position ``p`` writes draft-cache rows ``p..p+k-1`` before any
    of its queries attend them; the next window starts at the commit
    frontier ``p' <= p+k+1`` and rewrites every row a rejected draft
    polluted (``p'..p'+k-1`` covers ``p+a+1..p+k-1`` for any accept
    length ``a``) — the same write-before-attend induction the engine's
    slot reuse rides on, so rejected drafts never leak into a later
    window's attention."""

    def __init__(self, config: SpeculativeConfig, engine) -> None:
        import jax
        import jax.numpy as jnp

        from chainermn_tpu.models.transformer import init_kv_caches

        config.validate()
        model = config.draft_model
        if model.vocab_size != engine.model.vocab_size:
            raise ValueError(
                f"draft model vocab {model.vocab_size} != target vocab "
                f"{engine.model.vocab_size} — drafted token ids must be "
                "target token ids")
        if model.tensor_axis is not None or model.sequence_axis is not None:
            raise ValueError(
                "the draft model runs un-sharded (plain jit) — rebuild it "
                "with tensor_axis=None, sequence_axis=None")
        if model.max_len < engine.cache_len:
            raise ValueError(
                f"draft model max_len {model.max_len} < engine cache_len "
                f"{engine.cache_len}")
        self.config = config
        self.engine = engine
        self.model = model
        self.params = config.draft_params
        self._jnp = jnp
        self._caches = init_kv_caches(model, engine.n_slots,
                                      engine.cache_len)
        self._prefill_len = engine.prefill_len
        self._prefill_fn = jax.jit(self._prefill_body(),
                                   donate_argnums=(1,))
        self._decode_fn = jax.jit(self._decode_body(), donate_argnums=(1,))

    def _prefill_body(self):
        """One request's FULL prompt (the drafter has no prefix cache to
        discount a suffix against) through the slot's dense cache rows —
        gather the slot, run the padded prompt at positions
        ``[0, prefill_len)``, scatter it back. No sampling: the first
        drafted token always conditions on the engine's committed one."""
        import jax.numpy as jnp
        from jax import lax

        model, plen = self.model, self._prefill_len

        def body(params, caches, tokens, slot):
            slot_c = [
                {kk: lax.dynamic_slice_in_dim(c[kk], slot, 1, 0)
                 for kk in ("k", "v")}
                for c in caches
            ]
            pos = jnp.arange(plen, dtype=jnp.int32)[None, :]
            _, slot_c = model.apply(params, tokens, pos, kv_caches=slot_c)
            out = []
            for c, s in zip(caches, slot_c):
                buf = dict(c)
                for kk in ("k", "v"):
                    buf[kk] = lax.dynamic_update_slice_in_dim(
                        buf[kk], s[kk], slot, 0)
                out.append(buf)
            return out

        return body

    def _decode_body(self):
        """All-slots one-token greedy step — the engine's dense decode
        body minus sampler keys (argmax; drafts are proposals)."""
        import jax.numpy as jnp

        model = self.model

        def body(params, caches, tokens, pos, active):
            lg, caches = model.apply(params, tokens[:, None], pos[:, None],
                                     kv_caches=caches)
            nxt = jnp.argmax(lg[:, 0], axis=-1).astype(jnp.int32)
            nxt = jnp.where(active, nxt, jnp.zeros_like(nxt))
            return caches, nxt

        return body

    # -- slot lifecycle -------------------------------------------------- #

    def on_admit(self, slot: int, prompt, first_token: int) -> None:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        tokens = np.zeros((1, self._prefill_len), np.int32)
        tokens[0, : len(prompt)] = prompt
        jnp = self._jnp
        self._caches = self._prefill_fn(self.params, self._caches,
                                        jnp.asarray(tokens),
                                        jnp.int32(slot))

    def on_commit(self, slot: int, tokens) -> None:
        pass   # the draft caches advance inside propose()

    def on_release(self, slot: int) -> None:
        pass   # stale rows are masked until the next tenant overwrites

    def reset(self) -> None:
        from chainermn_tpu.models.transformer import init_kv_caches

        self._caches = init_kv_caches(self.model, self.engine.n_slots,
                                      self.engine.cache_len)

    # -- drafting --------------------------------------------------------- #

    def propose(self, k: int) -> np.ndarray:
        """Run ``k`` chained draft decode steps from the engine's commit
        frontier (``_token`` at ``_pos`` per slot). Tokens stay on device
        between steps; ONE fetch at the end returns ``[n_slots, k]``."""
        from chainermn_tpu.dataflow.dispatch import device_fetch

        jnp = self._jnp
        eng = self.engine
        tok = jnp.asarray(eng._token)
        active = jnp.asarray(eng._active)
        pos = jnp.asarray(eng._pos)
        drafts = []
        for j in range(k):
            self._caches, tok = self._decode_fn(
                self.params, self._caches, tok, pos + j, active)
            drafts.append(tok)
        stacked = device_fetch(jnp.stack(drafts, axis=1))
        return np.asarray(stacked, np.int32)

    # -- engine integration ------------------------------------------------ #

    def warmup(self) -> None:
        jnp = self._jnp
        eng = self.engine
        self._caches = self._prefill_fn(
            self.params, self._caches,
            jnp.zeros((1, self._prefill_len), jnp.int32), jnp.int32(0))
        z = jnp.zeros((eng.n_slots,), jnp.int32)
        self._caches, _ = self._decode_fn(
            self.params, self._caches, z, z,
            jnp.zeros((eng.n_slots,), bool))

    def watched_fns(self) -> dict:
        return {"spec_draft_prefill": self._prefill_fn,
                "spec_draft_decode": self._decode_fn}

    def compile_counts(self) -> dict:
        return {"draft_prefill": int(self._prefill_fn._cache_size()),
                "draft_decode": int(self._decode_fn._cache_size())}


def build_drafter(config: SpeculativeConfig, engine):
    """Engine hook: validate the config and build its drafter."""
    config.validate()
    if config.drafter == "draft":
        return DraftModelDrafter(config, engine)
    return NgramDrafter(config, engine)
