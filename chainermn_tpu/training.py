"""Canonical data-parallel training step.

The reference's per-step control flow lives in Chainer's Trainer/Updater
(SURVEY.md S1: ChainerMN only wraps the optimizer hook, S3.2). In the TPU
rebuild the equivalent "hot loop contract" is a single jitted SPMD program:
forward + backward + cross-rank gradient mean + optimizer update + BN-stat
sync, built here once and reused by ``chip_smoke.py``, the benchmark's
training harness, the examples and ``__graft_entry__``.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from chainermn_tpu.communicators.communicator_base import CommunicatorBase
from chainermn_tpu.monitor import annotate, instrument
from chainermn_tpu.ops.flash_attention import kernels_interpreted


_PALLAS_ATTENTION = ("flash", "ring_flash", "zigzag_flash", "ulysses_flash")


def classification_loss_fn(
    model,
    rest: dict,
    mutable: list,
    images,
    labels,
    train_kwargs: dict,
    label_smoothing: float,
):
    """``loss_fn(params) -> (loss, updated_collections)`` shared by the
    shard_map step below and the FSDP step (``parallel/fsdp.py``), so the
    training math — loss options, mutable-collection handling — can never
    diverge between layouts."""

    def loss_fn(p):
        if mutable:
            logits, updated = model.apply(
                {"params": p, **rest}, images, mutable=mutable, **train_kwargs
            )
        else:
            logits = model.apply({"params": p}, images, **train_kwargs)
            updated = {}
        if label_smoothing:
            targets = optax.smooth_labels(
                jax.nn.one_hot(labels, logits.shape[-1]), label_smoothing
            )
            loss = optax.softmax_cross_entropy(logits, targets).mean()
        else:
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, labels
            ).mean()
        return loss, updated

    return loss_fn


def make_classification_train_step(
    model,
    optimizer: optax.GradientTransformation,
    comm: CommunicatorBase,
    train_kwargs: Optional[dict] = None,
    label_smoothing: float = 0.0,
) -> Callable:
    """Build the per-rank step body (to be wrapped by :func:`jit_train_step`).

    ``variables`` is a flax variables dict ({'params', 'batch_stats', ...});
    mutable collections (BN running stats) are updated from the local batch
    and then cross-rank averaged inside the step, so evaluation state is
    replica-consistent by construction (the reference needs a separate
    AllreducePersistent pass for this; we keep that extension for parity but
    the canonical step doesn't need it).
    """
    train_kwargs = dict(train_kwargs or {})

    def step(variables, opt_state, images, labels):
        # profiler scope: every op this body traces carries the name in its
        # HLO metadata, so XProf device rows read as "train_step/..."
        with annotate("chainermn.train_step"):
            return step_body(variables, opt_state, images, labels)

    def step_body(variables, opt_state, images, labels):
        params = variables["params"]
        rest = {k: v for k, v in variables.items() if k != "params"}
        mutable = list(rest.keys())
        # Differentiate wrt a VARYING view of the (replicated) params: under
        # shard_map's replication-tracking semantics, grad-of-varying-loss
        # wrt invariant params would insert an automatic cross-rank psum in
        # the backward — the grads arriving at the optimizer would already be
        # SUMMED (n x the mean, a silent lr scale) and the communicator
        # strategy's own collective (packed buffers, wire dtype, two-level
        # meshes) would be bypassed. pcast keeps the grads per-rank local so
        # the multi-node optimizer owns the one true reduction.
        params_v = jax.tree_util.tree_map(
            lambda a: jax.lax.pcast(a, comm.axis_name, to="varying"), params
        )
        loss_fn = classification_loss_fn(
            model, rest, mutable, images, labels, train_kwargs, label_smoothing
        )
        (loss, updated), grads = jax.value_and_grad(loss_fn, has_aux=True)(params_v)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        # replica-consistent mutable state (BN running stats are tiny; one
        # extra small collective per step)
        synced = {
            k: jax.tree_util.tree_map(lambda a: comm.allreduce(a, "mean"), v)
            for k, v in updated.items()
        }
        new_variables = {"params": params, **synced}
        return new_variables, opt_state, comm.allreduce(loss, "mean")

    return step


def jit_train_step(
    model,
    optimizer: optax.GradientTransformation,
    comm: CommunicatorBase,
    donate: bool = True,
    train_kwargs: Optional[dict] = None,
    label_smoothing: float = 0.0,
    monitored: bool = True,
) -> Callable:
    """The full jitted SPMD train step over the communicator's mesh.

    Call as ``step(variables, opt_state, images, labels)`` with ``variables``/
    ``opt_state`` replicated and the batch rank-major (leading axis = global
    batch, sharded over the mesh). Buffer donation keeps params/opt-state
    updates in-place on HBM (the reference's grow-only arenas play this role,
    SURVEY.md S2.9).

    ``monitored=True`` (default) returns the step wrapped in
    :func:`chainermn_tpu.monitor.instrument`: step start/end events, a
    step counter + step-time histogram in the process registry, recompile
    detection, and periodic device-memory gauges — call-transparent
    (``lower``/``_cache_size`` still delegate to the jitted function) and
    a few host dict ops per step.
    """
    body = make_classification_train_step(
        model, optimizer, comm, train_kwargs, label_smoothing
    )
    data = comm.data_spec
    # ZeRO-style optimizers shard their state over the mesh (rank-major)
    opt_spec = getattr(optimizer, "state_spec", P())
    sm = comm.shard_map(
        body,
        in_specs=(P(), opt_spec, data, data),
        out_specs=(P(), opt_spec, P()),
        # ZeRO's all_gather'd updates and the 2D strategy's all_gather leg
        # both defeat static replication inference
        check_vma=getattr(optimizer, "check_vma", True)
        and getattr(comm, "check_vma", True),
    )
    donate_argnums = (0, 1) if donate else ()
    jitted = jax.jit(sm, donate_argnums=donate_argnums)
    return instrument(jitted, "train_step") if monitored else jitted


def _shard_positions(model, seq_axis, t_local):
    """Per-shard global positions under sequence sharding: a scalar base for
    contiguous layouts, a position VECTOR for zigzag (each shard holds one
    early + one late chunk; feed data permuted by
    :func:`~chainermn_tpu.parallel.sequence.zigzag_permutation`)."""
    if seq_axis is None:
        return 0
    idx = jax.lax.axis_index(seq_axis)
    if getattr(model, "attention", None) in ("zigzag", "zigzag_flash"):
        from chainermn_tpu.parallel.sequence import zigzag_positions

        return zigzag_positions(idx, jax.lax.axis_size(seq_axis), t_local)
    return idx * t_local


def _jit_tp_lm_train_step(
    model,
    optimizer: optax.GradientTransformation,
    comm: CommunicatorBase,
    tensor_axis: str,
    shard_sequence: bool,
    donate: bool,
    monitored: bool = True,
) -> Callable:
    """The tensor-parallel LM step (dispatched to by :func:`jit_lm_train_step`
    when the model was built with ``tensor_axis``).

    Uses the **global-objective** gradient pattern (parallel/tensor.py):
    params stay invariant, the loss is pmean'd over every mesh axis it varies
    on, and replication tracking assembles each leaf's exact global gradient
    — sliced TP leaves by psum of zero-padded slices, replicated leaves by
    averaging. Consequently ``optimizer`` must be a PLAIN optax transform:
    the grads arriving at it are already the global gradient, and a
    multi-node wrapper's extra mean would shrink them by the axis size.

    The batch shards over every communicator axis EXCEPT ``tensor_axis`` and
    the model's ``sequence_axis`` (pure TP on a flat comm = replicated
    batch; a hierarchical comm gives dp x tp). A model built with BOTH
    ``tensor_axis`` and a distinct ``sequence_axis`` (``attention='ring'|
    'ulysses'``) over a 3-axis mesh gives full **dp x sp x tp**: the
    sequence dimension shards over ``sequence_axis`` and each shard's
    ``pos_offset`` is threaded automatically.
    """
    from chainermn_tpu.parallel.tensor import global_objective

    axes = comm.axis_name
    axes = (axes,) if isinstance(axes, str) else tuple(axes)
    if tensor_axis not in axes:
        raise ValueError(
            f"model.tensor_axis={tensor_axis!r} is not one of the "
            f"communicator's mesh axes {axes}"
        )
    seq_axis = getattr(model, "sequence_axis", None)
    if shard_sequence and seq_axis is None:
        raise ValueError(
            "shard_sequence=True with a TP model needs the model built with "
            "sequence_axis (and attention='ring'|'zigzag'|'ulysses' or a "
            "_flash variant)"
        )
    if seq_axis is not None and (seq_axis == tensor_axis
                                 or seq_axis not in axes):
        raise ValueError(
            f"model.sequence_axis={seq_axis!r} must be a mesh axis distinct "
            f"from tensor_axis={tensor_axis!r} (mesh axes {axes})"
        )
    if seq_axis is not None and not shard_sequence:
        # mirror the dense path: a sequence_axis model under this step WILL
        # have its sequence sharded — a caller asking for shard_sequence=
        # False must not silently get sequence sharding anyway
        raise ValueError(
            f"model has sequence_axis={seq_axis!r}: the TP step shards the "
            "sequence over it — pass shard_sequence=True (or build the "
            "model without sequence_axis for batch-only sharding)"
        )
    if seq_axis is not None and getattr(model, "attention", None) not in (
            "ring", "ring_flash", "zigzag", "zigzag_flash", "ulysses",
            "ulysses_flash"):
        # 'full' under a sharded sequence silently computes block-diagonal
        # attention (each shard attends within its own chunk only)
        raise ValueError(
            f"sequence_axis={seq_axis!r} needs attention='ring'|'zigzag'|"
            f"'ulysses' (or _flash); got "
            f"{getattr(model, 'attention', None)!r} — plain "
            "'full' would attend within each sequence shard only"
        )
    if (getattr(model, "attention", None) in _PALLAS_ATTENTION
            and kernels_interpreted()):
        # The dense LM step works around interpret-mode Pallas by dropping
        # to check_vma=False; the TP step CANNOT (the global-objective
        # pattern is built on vma tracking — global_objective raises).
        raise ValueError(
            "tensor_axis + Pallas attention (flash/ring_flash) needs "
            "compiled TPU kernels; in interpret mode (non-TPU backends) the "
            "required check_vma=False would break the global-objective "
            "gradient pattern — use attention='full'/'ring' off-TPU"
        )
    dp_axes = tuple(a for a in axes if a != tensor_axis and a != seq_axis)

    vocab_parallel = getattr(model, "vocab_parallel_head", False)

    def body(params, opt_state, tokens, targets):
        with annotate("chainermn.lm_tp_train_step"):
            return body_inner(params, opt_state, tokens, targets)

    def body_inner(params, opt_state, tokens, targets):
        pos_offset = _shard_positions(model, seq_axis, tokens.shape[1])

        def loss_fn(p):
            logits = model.apply(p, tokens, pos_offset)
            if vocab_parallel:
                from chainermn_tpu.parallel.tensor import (
                    vocab_parallel_cross_entropy,
                )

                ce = vocab_parallel_cross_entropy(
                    logits, targets, tensor_axis
                ).mean()
            else:
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    logits, targets
                ).mean()
            return global_objective(ce, axes)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, new_opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        # uniform step arity with the dense/MoE path: stats is always there
        # (TP models are dense, so it is always empty here)
        return params, new_opt_state, loss, {}

    # batch dim over the dp axes, sequence dim over the model's seq axis
    data = P(dp_axes if dp_axes else None,
             seq_axis if seq_axis is not None else None)
    sm = comm.shard_map(
        body,
        in_specs=(P(), P(), data, data),
        out_specs=(P(), P(), P(), P()),
    )
    donate_argnums = (0, 1) if donate else ()
    jitted = jax.jit(sm, donate_argnums=donate_argnums)
    return instrument(jitted, "lm_tp_train_step") if monitored else jitted


def jit_lm_train_step(
    model,
    optimizer: optax.GradientTransformation,
    comm: CommunicatorBase,
    shard_sequence: bool = False,
    donate: bool = True,
    moe_aux_weight: float = 0.01,
    fused_ce: bool = False,
    monitored: bool = True,
) -> Callable:
    """Jitted next-token-prediction step for :class:`TransformerLM`-shaped
    models. Call as ``step(params, opt_state, tokens, targets)`` ->
    ``(params, opt_state, loss, stats)``. ``stats`` is a dict — ``{}`` for
    dense models; MoE models carry ``{'moe_drop_frac': ...}``: the
    globally-averaged fraction of expert assignments dropped to the
    capacity bound this step (silent drops were round 3's telemetry gap —
    log it; a persistently high value means the gate is unbalanced or
    capacity_factor is too small). The arity is uniform on purpose: it
    does not change under the model config (round-4 advisor finding).

    ``shard_sequence=False``: batch axis sharded over the mesh (pure DP).
    ``shard_sequence=True``: the SEQUENCE axis is sharded (context
    parallelism for long-context training) — build the model with
    ``attention='ring'``, ``'zigzag'`` (load-balanced causal; feed data
    permuted by :func:`~chainermn_tpu.parallel.sequence.zigzag_permutation`)
    or ``'ulysses'``, and ``sequence_axis=comm.axis_name``; each shard's
    global positions are threaded through ``pos_offset`` (a vector under
    zigzag). Gradients are averaged over the axis by the multi-node
    optimizer either way, so params stay replicated.

    ``monitored=True`` (default) wraps the jitted step in
    :func:`chainermn_tpu.monitor.instrument` (step events + metrics +
    recompile tracking), call-transparently — see :func:`jit_train_step`.
    """
    # Mismatched model/step configs run without error but compute the wrong
    # attention (the axis IS bound inside shard_map either way) — reject.
    attn = getattr(model, "attention", None)
    seq_axis = getattr(model, "sequence_axis", None)
    moe_experts = getattr(model, "moe_experts", 0)
    tensor_axis = getattr(model, "tensor_axis", None)
    if fused_ce and (tensor_axis is not None
                     or getattr(model, "vocab_parallel_head", False)):
        raise ValueError(
            "fused_ce applies the replicated lm_head itself; the TP/"
            "vocab-parallel paths shard the head and already avoid full "
            "logits (vocab_parallel_cross_entropy)"
        )
    if tensor_axis is not None:
        return _jit_tp_lm_train_step(
            model, optimizer, comm, tensor_axis,
            shard_sequence=shard_sequence, donate=donate,
            monitored=monitored,
        )
    if moe_experts and getattr(model, "moe_axis", None) != comm.axis_name:
        raise ValueError(
            f"MoE model must be built with moe_axis={comm.axis_name!r} "
            f"(got {getattr(model, 'moe_axis', None)!r}) so experts shard "
            "over the step's mesh axis"
        )
    if attn is not None:
        if shard_sequence:
            if (attn not in ("ring", "ring_flash", "zigzag", "zigzag_flash",
                             "ulysses", "ulysses_flash")
                    or seq_axis != comm.axis_name):
                raise ValueError(
                    f"shard_sequence=True needs the model built with "
                    f"attention='ring'|'ring_flash'|'zigzag'|'zigzag_flash'|"
                    f"'ulysses'(+_flash) and sequence_axis={comm.axis_name!r}; got "
                    f"attention={attn!r}, sequence_axis={seq_axis!r}"
                )
        elif seq_axis is not None:
            raise ValueError(
                f"model has sequence_axis={seq_axis!r} but shard_sequence="
                f"False shards the batch axis — the sequence-parallel "
                f"attention would mix different batch shards' K/V"
            )

    def body(params, opt_state, tokens, targets):
        with annotate("chainermn.lm_train_step"):
            return body_inner(params, opt_state, tokens, targets)

    def body_inner(params, opt_state, tokens, targets):
        pos_offset = _shard_positions(
            model, comm.axis_name if shard_sequence else None, tokens.shape[1]
        )
        # varying view for local grads — see make_classification_train_step
        params_v = jax.tree_util.tree_map(
            lambda a: jax.lax.pcast(a, comm.axis_name, to="varying"), params
        )

        def loss_fn(p):
            # return_hidden is passed ONLY when fused_ce asks for it: the
            # step's contract covers any TransformerLM-SHAPED model, and a
            # user model without the kwarg must keep working un-fused
            extra = {"return_hidden": True} if fused_ce else {}
            if moe_experts:
                (out, aux), sown = model.apply(
                    p, tokens, pos_offset, return_aux=True,
                    mutable=["moe_stats"], **extra,
                )
            else:
                out, aux, sown = model.apply(
                    p, tokens, pos_offset, **extra), 0.0, {}
            if fused_ce:
                # fused head+loss: the [B, T, vocab] f32 logits pair is the
                # step's largest tensor (scripts/lm_roofline_aot.jsonl) —
                # the chunked CE never builds it (ops/losses.py)
                from chainermn_tpu.ops.losses import (
                    chunked_softmax_cross_entropy,
                )

                head = p["params"]["lm_head"]
                ce = chunked_softmax_cross_entropy(
                    out, head["kernel"], head.get("bias"), targets
                ).mean()
            else:
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    out, targets
                ).mean()
            return ce + moe_aux_weight * aux, sown

        (loss, sown), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params_v)
        updates, new_opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        loss = comm.allreduce(loss, "mean")
        if not moe_experts:
            return params, new_opt_state, loss, {}
        # routing telemetry: mean drop fraction over the MoE layers (each
        # leaf is already pmean'd over the expert axis inside the module)
        from chainermn_tpu.parallel.moe import drop_frac_from_sown

        return params, new_opt_state, loss, {
            "moe_drop_frac": drop_frac_from_sown(sown)}

    data = P(None, comm.axis_name) if shard_sequence else comm.data_spec
    opt_spec = getattr(optimizer, "state_spec", P())
    # 4th slot is the stats dict: {} for dense (P() applies to no leaves)
    out_specs = (P(), opt_spec, P(), P())
    sm = comm.shard_map(
        body,
        in_specs=(P(), opt_spec, data, data),
        out_specs=out_specs,
        # Pallas interpret mode can't thread varying-manner metadata through
        # kernel-internal literals (JAX suggests check_vma=False as the
        # workaround); semantics are unchanged, only the static check is off.
        # Compiled TPU kernels don't need the workaround — keep the check on.
        # ZeRO's all_gather'd updates likewise defeat the static check.
        check_vma=(attn not in _PALLAS_ATTENTION or not kernels_interpreted())
        and getattr(optimizer, "check_vma", True)
        and getattr(comm, "check_vma", True),
    )
    donate_argnums = (0, 1) if donate else ()
    jitted = jax.jit(sm, donate_argnums=donate_argnums)
    return instrument(jitted, "lm_train_step") if monitored else jitted


def fit(
    step: Callable,
    variables,
    opt_state,
    data,
    n_steps: int,
    *,
    fetch_every: int = 8,
    prefetch_depth: int = 0,
    sharding=None,
    transform: Optional[Callable] = None,
    on_loss: Optional[Callable] = None,
    name: str = "fit",
) -> tuple:
    """The async hot loop: drive a jitted step ``n_steps`` times with
    dispatch-ahead loss handling and (optionally) device prefetch.

    The synchronous pattern — ``batch = next(data); ...; float(loss)``
    per step — pays host latencies on the critical path twice: the input
    side (assembly + H2D after the step instead of under it) and the
    output side (a device->host round trip per step). This loop
    pays neither: batches arrive device-resident from a
    :class:`~chainermn_tpu.dataflow.DevicePrefetcher` producer thread,
    and losses stay ON DEVICE in a
    :class:`~chainermn_tpu.dataflow.LossWindow`, fetched batched every
    ``fetch_every`` steps — one round trip closes the whole window and
    bounds in-flight dispatch at ``fetch_every`` steps.

    Parameters
    ----------
    step : callable
        ``step(variables, opt_state, x, y)`` returning
        ``(variables, opt_state, loss)`` (:func:`jit_train_step`) or
        ``(params, opt_state, loss, stats)`` (:func:`jit_lm_train_step`;
        ``stats`` is dropped here — drive MoE telemetry loops manually).
    data : iterator or iterable
        Yields ``(x, y)`` batch pairs. With ``prefetch_depth > 0`` it is
        wrapped in a ``DevicePrefetcher(depth=prefetch_depth,
        sharding=sharding, transform=transform)``; otherwise batches are
        fed as yielded (pass an already-wrapped prefetcher here to keep
        its ``state_dict`` under your control).
    fetch_every : int
        Loss-fetch cadence AND the in-flight dispatch bound.
        ``fetch_every=1`` degenerates to the synchronous per-step fetch.
    on_loss : callable, optional
        ``on_loss(step_index, float_loss)`` per loss, at fetch time
        (i.e. up to ``fetch_every - 1`` steps late).

    Returns
    -------
    ``(variables, opt_state, losses)`` — ``losses`` is every step's loss
    as floats, in step order; the trailing drain doubles as the loop's
    completion barrier, so on return all ``n_steps`` steps have finished
    on device.

    Every step runs inside a ``train_step`` trace (the monitor's tracing
    layer): child spans attribute the wall time to ``prefetch_wait``
    (drawing the batch — a stall here means the input pipeline is the
    bottleneck), ``dispatch`` (enqueueing the device step — async, so
    normally microseconds), and ``loss_fetch`` (the batched host round
    trip the loss window pays once per ``fetch_every`` steps). Sampled
    per the default tracer's config; disabled tracing costs one no-op
    call per step.
    """
    from chainermn_tpu.dataflow import DevicePrefetcher, LossWindow
    from chainermn_tpu.monitor.trace import get_tracer

    prefetcher = None
    if prefetch_depth:
        data = prefetcher = DevicePrefetcher(
            data, depth=prefetch_depth, sharding=sharding,
            transform=transform, name=name)
    it = data if hasattr(data, "__next__") else iter(data)
    window = LossWindow(fetch_every, name=name, on_fetch=on_loss)
    tracer = get_tracer()
    try:
        for i in range(n_steps):
            with tracer.trace("train_step", kind="train", step=i,
                              loop=name):
                with tracer.span("prefetch_wait"):
                    x, y = next(it)
                with tracer.span("dispatch"):
                    out = step(variables, opt_state, x, y)
                variables, opt_state = out[0], out[1]
                # a fetch inside push lands as a loss_fetch child span
                window.push(i, out[2])
        losses = window.drain()
    finally:
        if prefetcher is not None:
            prefetcher.close()
    return variables, opt_state, losses
