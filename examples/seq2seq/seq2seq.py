#!/usr/bin/env python
"""Seq2seq model-parallel training — encoder and decoder on different ranks.

Parity target: ``[U] examples/seq2seq/seq2seq.py`` (SURVEY.md S2.15 —
unverified cite): the reference trains a WMT encoder–decoder with the
encoder's NStepLSTM on rank 0 and the decoder on rank 1, wired by
differentiable ``send``/``recv``; ``seq2seq_mp1.py`` adds hybrid data x model
parallelism via ``comm.split`` (S2.16, med confidence).

TPU re-design: the chain is declared once (``MultiNodeChainList``); the
encoder's final GRU state crosses the rank boundary as a device-to-device
transfer whose autodiff transpose is the reference's backward ``recv``. The
task is synthetic sequence reversal (no corpus download): source = random
token sequence, target = its reverse — a real seq2seq task with non-trivial
alignment that a GRU encoder/decoder genuinely has to learn.

Hybrid DP x MP (``--hybrid``, needs >= 4 devices): devices are paired into
``size // 2`` model-parallel groups (pair g = ranks {2g, 2g+1}); each pair
trains a full encoder/decoder chain on its own batch shard, and gradients are
averaged *across pairs, per role* with a grouped collective on the
``comm.split``-derived communicator (even ranks = encoders, odd = decoders) —
the same split-by-color topology the reference's hybrid example builds.

Run (2+ emulated devices)::

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=2 \
        python examples/seq2seq/seq2seq.py --epoch 3
"""

from __future__ import annotations

import argparse
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

import chainermn_tpu
from chainermn_tpu.utils import enable_compilation_cache

BOS = 0  # decoder start token; task vocabulary occupies [1, vocab)


class Encoder(nn.Module):
    """Stage 0 (rank 0): embed source tokens, run a GRU, emit the final
    state. Passes the decoder inputs through untouched — in the reference
    both ranks read the batch; in the single-controller chain the boundary
    payload carries everything the next stage consumes."""

    vocab: int
    units: int

    @nn.compact
    def __call__(self, src, tgt_in):
        e = nn.Embed(self.vocab, self.units)(src)
        state, _ = nn.RNN(nn.GRUCell(self.units))(e, return_carry=True)
        return state, tgt_in


class Decoder(nn.Module):
    """Stage 1 (rank 1): teacher-forced GRU conditioned on the encoder
    state (received across the rank boundary), projecting to logits."""

    vocab: int
    units: int

    @nn.compact
    def __call__(self, inputs):
        state, tgt_in = inputs
        e = nn.Embed(self.vocab, self.units)(tgt_in)
        ys = nn.RNN(nn.GRUCell(self.units))(e, initial_carry=state)
        return nn.Dense(self.vocab)(ys)


def make_reversal_batch(rng, n, seq_len, vocab):
    """source: random tokens in [1, vocab); target: reversed source.
    Decoder input is the BOS-shifted target (teacher forcing)."""
    src = rng.randint(1, vocab, size=(n, seq_len)).astype(np.int32)
    tgt = src[:, ::-1].copy()
    tgt_in = np.concatenate([np.full((n, 1), BOS, np.int32), tgt[:, :-1]], axis=1)
    return src, tgt_in, tgt


def build_chain(comm, vocab, units, rank_enc, rank_dec):
    chain = chainermn_tpu.MultiNodeChainList(comm)
    chain.add_link(Encoder(vocab, units), rank=rank_enc, rank_in=None,
                   rank_out=rank_dec)
    chain.add_link(Decoder(vocab, units), rank=rank_dec, rank_in=rank_enc,
                   rank_out=None)
    return chain


def chain_loss(chain):
    def loss_fn(variables, src, tgt_in, tgt):
        logits = chain.apply(variables, src, tgt_in)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tgt
        ).mean()
    return loss_fn


def token_accuracy(chain, variables, src, tgt_in, tgt) -> float:
    logits = chain.apply(variables, src, tgt_in)
    pred = np.argmax(np.asarray(logits), axis=-1)
    return float((pred == tgt).mean())


def mean_grads_across_pairs(dp_comm, grads_per_pair, role, n_slots):
    """Average one role's gradient pytrees across the MP pairs with a grouped
    collective on the split communicator.

    The eager grouped allreduce takes rank-major arrays over ALL global ranks;
    pair g's role-``role`` grads sit in slot ``2g + role`` (their owning
    device rank) and the other role's slots are zero-padding whose group never
    mixes with ours (split color = rank % 2). Each pair's grads arrive
    committed to that pair's device, so packing stages through the host and
    the averaged result is committed back to each owner."""

    devices = list(dp_comm.mesh.devices.flat)

    def pack(*leaves):
        z = np.zeros((n_slots,) + leaves[0].shape, np.asarray(leaves[0]).dtype)
        for g, leaf in enumerate(leaves):
            z[2 * g + role] = np.asarray(jax.device_get(leaf))
        return jnp.asarray(z)

    packed = jax.tree_util.tree_map(pack, *grads_per_pair)
    meaned = jax.device_get(dp_comm.allreduce(packed, "mean"))
    return [
        jax.tree_util.tree_map(
            lambda l, s=2 * g + role: jax.device_put(l[s], devices[s]), meaned
        )
        for g in range(len(grads_per_pair))
    ]


def main() -> None:
    parser = argparse.ArgumentParser(
        description="ChainerMN-TPU example: seq2seq model parallelism"
    )
    parser.add_argument("--batchsize", "-b", type=int, default=64)
    parser.add_argument("--epoch", "-e", type=int, default=20)
    parser.add_argument("--unit", "-u", type=int, default=64)
    parser.add_argument("--vocab", type=int, default=16)
    parser.add_argument("--seq-len", type=int, default=8)
    parser.add_argument("--n-train", type=int, default=2048)
    parser.add_argument("--n-test", type=int, default=256)
    parser.add_argument("--hybrid", action="store_true",
                        help="data x model parallel over >= 4 devices "
                             "(comm.split by role, reference seq2seq_mp1)")
    args = parser.parse_args()
    enable_compilation_cache()

    chainermn_tpu.add_global_except_hook()
    comm = chainermn_tpu.create_communicator("naive")
    if comm.size < 2:
        raise SystemExit("seq2seq model-parallel example needs >= 2 devices")

    rng = np.random.RandomState(0)
    train = make_reversal_batch(rng, args.n_train, args.seq_len, args.vocab)
    test = make_reversal_batch(rng, args.n_test, args.seq_len, args.vocab)

    optimizer = optax.adam(2e-3)
    n_pairs = comm.size // 2 if args.hybrid else 1
    if args.hybrid and (comm.size < 4 or comm.size % 2):
        raise SystemExit(
            f"--hybrid needs an even device count >= 4 (2 per MP pair); "
            f"got {comm.size}"
        )

    # one chain per MP pair; identical init (same key) keeps pairs in sync,
    # the reference's bcast_data-at-start contract
    chains = [
        build_chain(comm, args.vocab, args.unit, 2 * g, 2 * g + 1)
        for g in range(n_pairs)
    ]
    variables = [
        c.init(jax.random.PRNGKey(0), jnp.asarray(train[0][:1]),
               jnp.asarray(train[1][:1]))
        for c in chains
    ]
    opt_states = [[optimizer.init(v) for v in vs] for vs in variables]
    grad_fns = [jax.value_and_grad(chain_loss(c)) for c in chains]
    dp_comm = (
        comm.split([r % 2 for r in range(comm.size)]) if args.hybrid else None
    )

    steps_per_epoch = max(1, args.n_train // args.batchsize)
    t0 = time.time()
    for epoch in range(1, args.epoch + 1):
        perm = rng.permutation(args.n_train)
        losses = []
        for it in range(steps_per_epoch):
            idx = perm[it * args.batchsize:(it + 1) * args.batchsize]
            shards = np.array_split(idx, n_pairs)
            grads_all, loss_sum = [], 0.0
            for g in range(n_pairs):
                src, tgt_in, tgt = (a[shards[g]] for a in train)
                loss, grads = grad_fns[g](variables[g], src, tgt_in, tgt)
                grads_all.append(grads)
                loss_sum += float(loss)
            if dp_comm is not None:
                # grads_all[g] is a 2-list [enc_grads, dec_grads]
                for role in range(2):
                    meaned = mean_grads_across_pairs(
                        dp_comm, [gs[role] for gs in grads_all], role, comm.size
                    )
                    for g in range(n_pairs):
                        grads_all[g][role] = meaned[g]
            for g in range(n_pairs):
                new_vs, new_ss = [], []
                for v, gr, s in zip(variables[g], grads_all[g], opt_states[g]):
                    updates, s = optimizer.update(gr, s, v)
                    new_vs.append(optax.apply_updates(v, updates))
                    new_ss.append(s)
                variables[g], opt_states[g] = new_vs, new_ss
            losses.append(loss_sum / n_pairs)
        if comm.rank == 0:
            acc = token_accuracy(chains[0], variables[0], *test)
            print(f"epoch {epoch:3d}  train/loss {np.mean(losses):.4f}  "
                  f"val/token_acc {acc:.4f}")
    if comm.rank == 0:
        print(f"done in {time.time() - t0:.1f}s  "
              f"(pairs={n_pairs}, hybrid={args.hybrid})")


if __name__ == "__main__":
    main()
