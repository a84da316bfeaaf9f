"""Mesh-backed communicator: the concrete core of the framework.

Re-designs the reference's ``MpiCommunicatorBase``
(``[U] chainermn/communicators/mpi_communicator_base.py``, SURVEY.md S2.2 —
unverified cite) for single-controller SPMD: instead of issuing MPI/NCCL calls
per collective, this class owns a ``jax.sharding.Mesh`` and lowers each
collective to the corresponding XLA op — directly when called on tracers
inside ``shard_map``/``pjit`` (the hot path, fused into the step program), or
through a cached ``jit(shard_map(...))`` harness when called eagerly on
rank-major arrays (the test/bootstrap path). See DESIGN.md.

The reference's chunked-transfer machinery (32-bit MPI count limits), typed
``_MessageType`` headers, and pinned-buffer staging have no equivalent here *by
design*: XLA owns buffering and transport on ICI, and arbitrary-object traffic
rides the process-space object comm (``_object_comm.py``).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chainermn_tpu.communicators import _object_comm
from chainermn_tpu.communicators.communicator_base import CommunicatorBase, ReduceOp
from chainermn_tpu.monitor import annotate
from chainermn_tpu.parallel import mesh as mesh_lib
from chainermn_tpu.resilience.cutpoints import COMM_ALLGATHER_OBJ, comm_point
from chainermn_tpu.resilience.faults import inject


def _is_traced(x) -> bool:
    return any(
        isinstance(leaf, jax.core.Tracer) for leaf in jax.tree_util.tree_leaves(x)
    )


def _leaf_vma(leaf):
    """The mesh axes a traced value varies over (its varying manner), or
    ``None`` when unavailable/untracked (e.g. ``check_vma=False`` tracing) —
    callers must then assume fully varying, the conservative default for
    gradient leaves."""
    try:
        vma = jax.typeof(leaf).vma
        return vma if isinstance(vma, frozenset) else frozenset(vma)
    except Exception:
        return None


class _MessageType(NamedTuple):
    """Typed p2p header: structure + per-leaf metadata, sent before the raw
    buffers — the descendant of the reference's ``_MessageType`` (shape/
    dtype/tuple-structure of ndarray trees, ``[U] .../mpi_communicator_base
    .py`` SURVEY.md S2.2). Dtypes are carried as ``np.dtype`` objects so
    extended dtypes (bfloat16 via ml_dtypes) round-trip exactly."""

    treedef: Any
    shapes: tuple[tuple[int, ...], ...]
    dtypes: tuple[np.dtype, ...]


class MeshCommunicator(CommunicatorBase):
    """Communicator over one flat mesh axis (or a tuple of axes treated as
    one flattened rank space — the hierarchical subclasses use that)."""

    # Whether steps traced over this communicator can keep shard_map's static
    # replication (VMA) check on. Strategies whose lowering contains an
    # all_gather that is provably-but-not-statically replicated (currently
    # TwoDimensionalCommunicator) set this False; comm.shard_map and the
    # training-step builders read it.
    check_vma = True

    def __init__(
        self,
        mesh: Mesh | None = None,
        axis_name: str | tuple[str, ...] | None = None,
        devices: Sequence[jax.Device] | None = None,
        _groups: list[list[int]] | None = None,
    ) -> None:
        if mesh is None:
            mesh = mesh_lib.make_mesh(devices)
        self._mesh = mesh
        if axis_name is None:
            axes: tuple[str, ...] = tuple(mesh.axis_names)
        elif isinstance(axis_name, str):
            axes = (axis_name,)
        else:
            axes = tuple(axis_name)
        for a in axes:
            if a not in mesh.axis_names:
                raise ValueError(f"axis {a!r} not in mesh axes {mesh.axis_names}")
        self._axes = axes
        self._geom = mesh_lib.RankGeometry.from_mesh(mesh)
        self._groups = _groups  # set on split() sub-communicators
        if _groups is not None:
            gsize = len(_groups[0])
            if any(len(g) != gsize for g in _groups):
                raise ValueError(
                    "split() groups must be equal-sized (XLA collective "
                    "requirement; the reference's MPI split has no such "
                    "constraint — pad colors if you need ragged groups)"
                )
            table = np.full(self._global_size, -1, np.int32)
            for g in _groups:
                for local, glob in enumerate(g):
                    table[glob] = local
            if (table < 0).any():
                raise ValueError("split() groups must cover every rank")
            self._local_rank_table = table
        self._cache: dict[Any, Callable] = {}
        self._mailbox: dict[tuple[int, int], list[Any]] = {}
        self._obj = _object_comm.create_object_comm()

    # ------------------------------------------------------------------ #
    # Topology                                                            #
    # ------------------------------------------------------------------ #

    @property
    def mesh(self) -> Mesh:
        return self._mesh

    @property
    def axis_name(self):
        """The communicator axis (str, or tuple for hierarchical meshes)."""
        return self._axes if len(self._axes) > 1 else self._axes[0]

    @property
    def _global_size(self) -> int:
        return int(np.prod([self._mesh.shape[a] for a in self._axes]))

    @property
    def size(self) -> int:
        return len(self._groups[0]) if self._groups else self._global_size

    @property
    def rank(self) -> int:
        return self._geom.rank

    @property
    def intra_rank(self) -> int:
        return self._geom.intra_rank

    @property
    def inter_rank(self) -> int:
        return self._geom.inter_rank

    @property
    def intra_size(self) -> int:
        return self._geom.intra_size

    @property
    def inter_size(self) -> int:
        return self._geom.inter_size

    @property
    def process_size(self) -> int:
        return self._geom.process_size

    def axis_index(self):
        """Traced rank (group-local on split communicators)."""
        idx = lax.axis_index(self._axes)
        if self._groups is not None:
            idx = jnp.asarray(self._local_rank_table)[idx]
        return idx

    # ------------------------------------------------------------------ #
    # Sharding conveniences (TPU extensions)                              #
    # ------------------------------------------------------------------ #

    def named_sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self._mesh, P(*spec))

    @property
    def data_spec(self) -> P:
        """PartitionSpec sharding a leading batch axis over the comm axis."""
        return P(self._axes if len(self._axes) > 1 else self._axes[0])

    def shard_map(self, f, in_specs, out_specs, check_vma: bool | None = None):
        """``jax.shard_map`` bound to this communicator's mesh. ``check_vma``
        defaults to the communicator's own :attr:`check_vma` (strategies with
        statically-unprovable replication turn the check off)."""
        if check_vma is None:
            check_vma = self.check_vma
        return jax.shard_map(
            f, mesh=self._mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=check_vma,
        )

    # ------------------------------------------------------------------ #
    # Traced collective bodies (group-aware)                              #
    # ------------------------------------------------------------------ #

    # Every traced collective body is wrapped in monitor.annotate: the XLA
    # ops carry a ``chainermn.<op>`` scope in their HLO metadata, so an
    # XProf/Perfetto capture shows WHICH framework collective a device-time
    # span belongs to. (Scope names avoid hyphenated opcode spellings so
    # parse_hlo_collectives' text scan can never match them.)

    def _gathered(self, x):
        """all_gather giving every rank the full [size, ...] stack; the
        building block for ops XLA lacks a grouped/native primitive for."""
        with annotate("chainermn.allgather"):
            return lax.all_gather(
                x, self._axes, axis_index_groups=self._groups, tiled=False
            )

    def _grouped_sum(self, x):
        """Group-scoped sum with ring-allreduce wire cost (~2x payload).

        ``lax.psum(axis_index_groups=...)`` is NotImplemented under shard_map
        in current JAX, but ``psum_scatter`` and ``all_gather`` both take
        groups — so decompose the allreduce the way the ring algorithm does:
        reduce-scatter a 1/n shard to each group member, then all-gather the
        shards back. (The previous fallback all-gathered the full payload:
        group_size x the bytes.)"""
        n = self.size

        def leaf(a):
            flat = jnp.ravel(a)
            pad = (-flat.size) % n
            if pad:
                flat = jnp.pad(flat, (0, pad))
            shard = lax.psum_scatter(
                flat.reshape(n, -1), self._axes, scatter_dimension=0,
                tiled=False, axis_index_groups=self._groups,
            )
            full = lax.all_gather(
                shard, self._axes, axis_index_groups=self._groups, tiled=False
            ).reshape(-1)
            if pad:
                full = full[: flat.size - pad]
            return full.reshape(a.shape)

        return jax.tree_util.tree_map(leaf, x)

    # Below this many bytes per leaf, prod uses one all_gather + local
    # reduce (one collective, size x bytes — fine for the typical tiny
    # operands); above it, the ring decomposition (2x payload wire,
    # O(payload) memory, n-1 latency steps).
    _PROD_RING_THRESHOLD = 1 << 16

    def _prod(self, x):
        """Allreduce-prod. XLA has no prod collective and psum_scatter can't
        carry the op, so this is either gather+reduce (small leaves) or a
        ring reduce-scatter in multiply (large leaves) — the same
        decomposition `_grouped_sum` uses, with ppermute because the
        reduction op must be ours."""
        ring_ok = self.size > 1

        def leaf(a):
            if not ring_ok or a.size * a.dtype.itemsize <= self._PROD_RING_THRESHOLD:
                return jnp.prod(self._gathered(a), axis=0)
            return self._ring_prod_leaf(a)

        return jax.tree_util.tree_map(leaf, x)

    def _ring_prod_leaf(self, a):
        """Ring allreduce with multiply: after s hops the carry that will end
        at group slot q has visited slots q-s..q-1, each multiplying in its
        local block for index q; an all_gather of the finished blocks
        rebuilds the full product. Works grouped (ring within each group),
        ungrouped, and on multi-axis meshes (ppermute linearizes tuple axes
        exactly as axis_index does)."""
        axis = self._axes
        n = self.size
        pos = self.axis_index()
        flat = jnp.ravel(a)
        pad = (-flat.size) % n
        if pad:  # pad value never survives the final slice; ones for tidiness
            flat = jnp.concatenate([flat, jnp.ones((pad,), flat.dtype)])
        blocks = flat.reshape(n, -1)
        if self._groups is None:
            perm = [(i, (i + 1) % n) for i in range(n)]
        else:
            perm = [(g[i], g[(i + 1) % len(g)])
                    for g in self._groups for i in range(len(g))]

        def block_for(s):
            return jnp.take(blocks, jnp.mod(pos - s - 1, n), axis=0)

        carry = block_for(0)
        for s in range(1, n):
            carry = lax.ppermute(carry, axis, perm)
            carry = carry * block_for(s)
        full = lax.all_gather(
            carry, axis, axis_index_groups=self._groups, tiled=False
        ).reshape(-1)
        if pad:
            full = full[: flat.size - pad]
        return full.reshape(a.shape)

    def _t_allreduce(self, x, op: ReduceOp):
        with annotate(f"chainermn.allreduce_{op}"):
            return self._t_allreduce_body(x, op)

    def _t_allreduce_body(self, x, op: ReduceOp):
        if op == "prod":
            return self._prod(x)
        if self._groups is None:
            if op == "sum":
                return lax.psum(x, self._axes)
            if op == "mean":
                return lax.pmean(x, self._axes)
            if op == "max":
                return lax.pmax(x, self._axes)
            if op == "min":
                return lax.pmin(x, self._axes)
            raise ValueError(f"unknown reduce op {op!r}")
        if op == "max":
            return lax.pmax(x, self._axes, axis_index_groups=self._groups)
        if op == "min":
            return lax.pmin(x, self._axes, axis_index_groups=self._groups)
        if op == "sum":
            return self._grouped_sum(x)
        if op == "mean":
            return jax.tree_util.tree_map(
                lambda s: s / self.size, self._grouped_sum(x)
            )
        raise ValueError(f"unknown reduce op {op!r}")

    def _t_bcast(self, x, root: int):
        # Masked sum: only root contributes, everyone ends with root's value.
        # Ungrouped this is one psum (~2x-of-optimal ring traffic, payload-
        # sized HLO output — independent of mesh size); grouped it rides the
        # reduce-scatter/all-gather decomposition. (A true collective-
        # broadcast would halve wire bytes, but JAX exposes neither
        # collective-broadcast nor multi-destination ppermute.)
        with annotate("chainermn.bcast"):
            mask = self.axis_index() == root
            masked = jax.tree_util.tree_map(
                lambda a: jnp.where(mask, a, jnp.zeros_like(a)), x
            )
            if self._groups is None:
                return lax.psum(masked, self._axes)
            return self._grouped_sum(masked)

    def _t_gather(self, x, root: int):
        del root  # SPMD: the stack is global; "root-ness" is a sharding choice
        return self._gathered(x)

    def _t_allgather(self, x):
        return self._gathered(x)

    def _t_scatter(self, x, root: int):
        # Masked reduce-scatter: root's [size, ...] array is the only nonzero
        # contribution, so the summed shard each rank receives IS its slice.
        # O(payload) on the wire vs the previous bcast-the-whole-array+slice
        # (which shipped size x the useful bytes); works grouped too.
        if x.shape[0] != self.size:
            raise ValueError(
                f"scatter input leading axis {x.shape[0]} != comm size {self.size}"
            )
        with annotate("chainermn.scatter"):
            mask = self.axis_index() == root
            masked = jnp.where(mask, x, jnp.zeros_like(x))
            return lax.psum_scatter(
                masked, self._axes, scatter_dimension=0, tiled=False,
                axis_index_groups=self._groups,
            )

    def _t_alltoall(self, x):
        if x.shape[0] != self.size:
            raise ValueError(
                f"alltoall input leading axis {x.shape[0]} != comm size {self.size}"
            )
        with annotate("chainermn.alltoall"):
            return lax.all_to_all(
                x, self._axes, split_axis=0, concat_axis=0, tiled=True,
                axis_index_groups=self._groups,
            )

    def _t_ppermute(self, x, perm: Sequence[tuple[int, int]]):
        """Group-local perm pairs -> global pairs when split."""
        if self._groups is not None:
            perm = [(g[s], g[d]) for g in self._groups for (s, d) in perm]
        with annotate("chainermn.ppermute"):
            return lax.ppermute(x, self._axes, perm=list(perm))

    # ------------------------------------------------------------------ #
    # Eager harness: rank-major arrays through cached jit(shard_map)      #
    # ------------------------------------------------------------------ #

    def _eager(self, opname: str, body: Callable, args, extra_key=()):
        """Run ``body`` (written against per-rank local arrays) over
        rank-major global inputs. ``args`` is a tuple; each element is a
        pytree whose every leaf has leading axis == global size."""
        # fault cut-point: the host boundary of every eager collective
        # (traced collectives fuse into compiled programs and cannot host-
        # inject — a device-program failure is the engine/step boundary's
        # scenario, exercised at serving.*/trainer.step instead)
        inject(comm_point(opname))
        leaves, treedef = jax.tree_util.tree_flatten(args)
        gsize = self._global_size
        multiproc = jax.process_count() > 1
        if multiproc:
            # Multi-controller: every process passes the same rank-major host
            # array; ONE device_put with the global sharding moves just this
            # process's addressable shards. (A jnp.asarray commit first would
            # pay a full-array transfer before resharding.) Outputs are
            # global jax.Arrays — read your shard via .addressable_data(0).
            sharding = NamedSharding(self._mesh, self.data_spec)
            leaves = [
                jax.device_put(np.asarray(l), sharding) for l in leaves
            ]
        else:
            leaves = [jnp.asarray(l) for l in leaves]
        for l in leaves:
            if l.ndim < 1 or l.shape[0] != gsize:
                raise ValueError(
                    f"{opname}: eager collectives take rank-major arrays "
                    f"(leading axis == {gsize}); got shape {l.shape}. "
                    "Inside shard_map/pjit, pass tracers instead."
                )
        key = (
            opname,
            treedef,
            tuple((l.shape, str(l.dtype)) for l in leaves),
            extra_key,
        )
        fn = self._cache.get(key)
        if fn is None:
            spec = self.data_spec

            def wrapper(*flat_local):
                local = jax.tree_util.tree_unflatten(
                    treedef, [l[0] for l in flat_local]
                )
                out = body(*local)  # args is always a tuple of pytrees
                return jax.tree_util.tree_map(lambda o: o[None, ...], out)

            fn = jax.jit(
                jax.shard_map(
                    wrapper, mesh=self._mesh, in_specs=spec, out_specs=spec
                )
            )
            self._cache[key] = fn
        return fn(*leaves)

    # ------------------------------------------------------------------ #
    # Public array collectives (dual dispatch)                            #
    # ------------------------------------------------------------------ #

    def allreduce(self, x, op: ReduceOp = "sum"):
        if _is_traced(x):
            return self._t_allreduce(x, op)
        return self._eager("allreduce", lambda a: self._t_allreduce(a, op), (x,), op)

    def bcast(self, x, root: int = 0):
        if _is_traced(x):
            return self._t_bcast(x, root)
        return self._eager("bcast", lambda a: self._t_bcast(a, root), (x,), root)

    def gather(self, x, root: int = 0):
        if _is_traced(x):
            return self._t_gather(x, root)
        out = self._eager("gather", lambda a: self._t_gather(a, root), (x,), root)
        return out[0] if self._groups is None else out

    def allgather(self, x):
        if _is_traced(x):
            return self._t_allgather(x)
        return self._eager("allgather", self._t_allgather, (x,))

    def scatter(self, x, root: int = 0):
        if _is_traced(x):
            return self._t_scatter(x, root)
        return self._eager("scatter", lambda a: self._t_scatter(a, root), (x,), root)

    def alltoall(self, x):
        if _is_traced(x):
            return self._t_alltoall(x)
        return self._eager("alltoall", self._t_alltoall, (x,))

    def ppermute(self, x, perm: Sequence[tuple[int, int]]):
        """Rotate arrays along an explicit (source, dest) permutation —
        the primitive under functions.send/recv. *TPU extension*."""
        if _is_traced(x):
            return self._t_ppermute(x, perm)
        return self._eager(
            "ppermute", lambda a: self._t_ppermute(a, perm), (x,), tuple(perm)
        )

    # ------------------------------------------------------------------ #
    # Host-side p2p (process space)                                       #
    # ------------------------------------------------------------------ #

    def _check_process_rank(self, who: str, r: int) -> None:
        n = max(1, jax.process_count())
        if not 0 <= r < n:
            raise ValueError(
                f"{who}={r} out of range: host-side send/recv are *process*-"
                f"space (0..{n - 1}), mirroring the reference's per-process "
                "MPI p2p. For device-rank p2p inside a step, use "
                "chainermn_tpu.functions.send/recv (differentiable, "
                "ppermute-based)."
            )

    def send(self, x, dest: int, tag: int = 0) -> None:
        """Typed p2p send of an **array pytree** (single arrays included):
        a ``_MessageType`` header (treedef, shapes, dtypes) goes first, then
        one raw buffer per leaf — the reference's ndarray-tree ``send``
        protocol, re-hosted on the object transport. ``recv`` reconstructs
        the exact structure and dtypes."""
        if _is_traced(x):
            raise RuntimeError(
                "comm.send inside traced code: use chainermn_tpu.functions."
                "send (differentiable, ppermute-based) for in-step p2p."
            )
        self._check_process_rank("dest", dest)
        leaves, treedef = jax.tree_util.tree_flatten(x)
        arrays = [np.asarray(l) for l in leaves]
        header = _MessageType(
            treedef,
            tuple(a.shape for a in arrays),
            tuple(a.dtype for a in arrays),
        )
        if dest == self.rank:
            # copy: the remote path hands the receiver fresh buffers, so the
            # self-send path must too (no sender/receiver aliasing)
            self._mailbox.setdefault(tag, []).append(
                (header, [np.array(a) for a in arrays])
            )
        else:
            self._obj.send_obj(header, dest, tag)
            for a in arrays:
                self._obj.send_obj(np.ascontiguousarray(a).tobytes(), dest, tag)

    def recv(self, source: int, tag: int = 0):
        """Receive an array pytree sent by :meth:`send`: header first, then
        the leaf buffers, reassembled to the sent structure (a bare array in
        comes back as a bare array). Leaves come back as **numpy** arrays
        with the exact sent dtypes (f64 included — ``jnp.asarray`` would
        silently downcast without x64 mode); pass them straight into jitted
        code or ``device_put`` as needed."""
        self._check_process_rank("source", source)
        if source == self.rank:
            q = self._mailbox.get(tag)
            if not q:
                raise RuntimeError(f"recv(source={source}, tag={tag}): nothing sent")
            header, arrays = q.pop(0)
        else:
            header = self._obj.recv_obj(source, tag)
            if not isinstance(header, _MessageType):
                raise RuntimeError(
                    f"recv(source={source}, tag={tag}): expected a "
                    f"_MessageType header, got {type(header).__name__} — "
                    "pair comm.recv with comm.send (use recv_obj for "
                    "send_obj traffic)"
                )
            arrays = [
                np.frombuffer(
                    self._obj.recv_obj(source, tag), dtype=dt
                ).reshape(shape)
                for shape, dt in zip(header.shapes, header.dtypes)
            ]
        return jax.tree_util.tree_unflatten(header.treedef, list(arrays))

    # ------------------------------------------------------------------ #
    # Object communication (delegates to process-space transport)         #
    # ------------------------------------------------------------------ #

    def send_obj(self, obj, dest: int, tag: int = 0) -> None:
        self._obj.send_obj(obj, dest, tag)

    def recv_obj(self, source: int, tag: int = 0):
        return self._obj.recv_obj(source, tag)

    def bcast_obj(self, obj, root: int = 0):
        return self._obj.bcast_obj(obj, root)

    def gather_obj(self, obj, root: int = 0):
        return self._obj.gather_obj(obj, root)

    def allgather_obj(self, obj):
        # cut-point: the host object channel the checkpoint agreement and
        # registry aggregation ride (a raise here = a lost DCN peer)
        inject(COMM_ALLGATHER_OBJ)
        return self._obj.allgather_obj(obj)

    def allreduce_obj(self, obj, reduce_func: Callable | None = None):
        return self._obj.allreduce_obj(obj, reduce_func)

    def scatter_obj(self, objs, root: int = 0):
        return self._obj.scatter_obj(objs, root)

    def barrier(self) -> None:
        """Host-side barrier across processes (TPU extension; the reference
        leans on MPI's implicit collective synchronization)."""
        self._obj.barrier()

    # ------------------------------------------------------------------ #
    # Model helpers                                                       #
    # ------------------------------------------------------------------ #

    def bcast_data(self, params):
        """Replicate a parameter pytree across the mesh (reference
        ``bcast_data(model)`` — rank 0's weights to everyone). On multi-host,
        process 0's values win via a host broadcast first."""
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            params = multihost_utils.broadcast_one_to_all(params)
        sharding = NamedSharding(self._mesh, P())
        return jax.device_put(params, sharding)

    def _mean_leaves_traced(self, leaves: list):
        """Strategy hook: how a list of gradient leaves becomes a list of
        cross-rank means. Base = per-parameter collectives, the reference's
        ``NaiveCommunicator`` strategy (one MPI_Allreduce per param,
        ``[U] .../naive_communicator.py``)."""
        return [self._t_allreduce(g, "mean") for g in leaves]

    def multi_node_mean_grad(self, grads, zero_fill: bool = False):
        del zero_fill  # jax.grad never yields missing leaves; kept for parity
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        if not leaves:
            return grads
        if _is_traced(grads):
            # The contract is "mean of the per-rank gradients". Leaves that
            # shard_map's replication tracking marks INVARIANT along a comm
            # axis are already equal across that axis — their mean over it is
            # the value itself, so that axis needs NO collective (running the
            # strategy psum anyway would both waste wire bytes and, worse,
            # SUM the equal copies into size x the mean). This matters
            # because differentiating wrt replicated params with a
            # cross-rank-reduced loss auto-psums the backward: the arriving
            # grads are the correct global gradient, already invariant (see
            # test_hand_written_step... in tests/test_training_step.py; our
            # own step builders instead pcast params to varying so the
            # strategy owns the collective). With check_vma=False, tracking
            # is off and every value reports an empty vma — probe a
            # known-varying value so untracked local grads still take the
            # strategy path.
            tracking = bool(_leaf_vma(lax.axis_index(self._axes)))
            if self._groups is None and tracking:
                axes = set(self._axes)
                vmas = [_leaf_vma(l) for l in leaves]
                pending = [
                    i for i, v in enumerate(vmas)
                    if v is not None and not axes.issubset(v)
                ]
                if pending:
                    out = list(leaves)
                    for i in pending:
                        # pmean over the still-varying comm axes only;
                        # fully-invariant leaves pass through untouched
                        rest = tuple(a for a in self._axes if a in vmas[i])
                        out[i] = lax.pmean(leaves[i], rest) if rest else leaves[i]
                    varying = [i for i in range(len(leaves)) if i not in pending]
                    if varying:
                        meaned = self._mean_leaves_traced(
                            [leaves[i] for i in varying]
                        )
                        for i, m in zip(varying, meaned):
                            out[i] = m
                    return jax.tree_util.tree_unflatten(treedef, out)
            return jax.tree_util.tree_unflatten(
                treedef, self._mean_leaves_traced(leaves)
            )

        def body(tree):
            ls, td = jax.tree_util.tree_flatten(tree)
            return jax.tree_util.tree_unflatten(td, self._mean_leaves_traced(ls))

        return self._eager("mean_grad", body, (grads,))

    # ------------------------------------------------------------------ #
    # Split & lifecycle                                                   #
    # ------------------------------------------------------------------ #

    def split(self, color, key=None) -> "MeshCommunicator":
        del key  # rank order within a color group follows device-rank order
        colors = list(color)
        if len(colors) != self._global_size:
            raise ValueError(
                f"split(): need one color per device rank ({self._global_size}); "
                f"got {len(colors)}. (The reference's per-process color arg is "
                "passed gathered in the SPMD re-design — see DESIGN.md.)"
            )
        groups: dict[Any, list[int]] = {}
        for r, c in enumerate(colors):
            groups.setdefault(c, []).append(r)
        return self._make_split([groups[c] for c in sorted(groups)])

    def _make_split(self, groups: list[list[int]]) -> "MeshCommunicator":
        """Same class, same mesh, group-scoped collectives. Strategy
        subclasses keep their identity (and copy extra state via
        :meth:`_copy_strategy_state`); their ``_mean_leaves_traced`` overrides
        see ``_groups`` and fall back where the strategy needs full-axis
        structure (the hierarchical pair)."""
        sub = object.__new__(type(self))
        MeshCommunicator.__init__(
            sub, mesh=self._mesh, axis_name=self._axes, _groups=groups
        )
        self._copy_strategy_state(sub)
        return sub

    def _copy_strategy_state(self, sub: "MeshCommunicator") -> None:
        """Hook: copy subclass-held config onto a split() child (overridden
        e.g. by TpuCommunicator for ``allreduce_grad_dtype``)."""

    def finalize(self) -> None:
        self._cache.clear()

    def __repr__(self) -> str:
        g = f", groups={self._groups}" if self._groups else ""
        return (
            f"<{type(self).__name__} size={self.size} axes={self._axes} "
            f"mesh={dict(self._mesh.shape)}{g}>"
        )
