"""Profiler annotations: one context manager for host AND traced code.

``annotate(name)`` enters both ``jax.profiler.TraceAnnotation`` (host-side
TraceMe — the region shows up on the Python/host rows of an XProf/Perfetto
capture) and ``jax.named_scope`` (trace-time name stack — the region's XLA
ops carry the name in their metadata, so device rows are legible too).
Entering them is cheap when no profiler is attached, so the annotations
stay on permanently in the hot paths (train step bodies, serving
prefill/decode, communicator collectives).

``annotate(name, **stats)`` hands keyword statistics to the TraceMe: the
event keeps its plain name and the numbers show as its arguments in
XProf/Perfetto (counts where the work happens: live slots of a decode
step, rows of a prefill program).

Scope names deliberately avoid XLA collective opcode spellings
(``all-reduce`` etc.): names land in HLO ``op_name`` metadata, and
:func:`~chainermn_tpu.extensions.profiling.parse_hlo_collectives` scans raw
HLO text — ``chainermn.allreduce`` can never collide with ``all-reduce(``.
"""

from __future__ import annotations

class _Annotation:
    """Re-entrant-constructible, single-use context manager pair."""

    __slots__ = ("_name", "_stats", "_tm", "_ns")

    def __init__(self, name: str, stats: dict) -> None:
        self._name = name
        self._stats = stats
        self._tm = None
        self._ns = None

    def __enter__(self) -> "_Annotation":
        # lazy: monitor must stay importable without jax (fleet/deploy
        # ride monitor at module level and are pure host-logic imports);
        # by the time an annotation is *entered*, jax is already loaded
        # by whatever produced the work being annotated
        import jax

        self._tm = jax.profiler.TraceAnnotation(self._name, **self._stats)
        self._tm.__enter__()
        self._ns = jax.named_scope(self._name)
        self._ns.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        if self._ns is not None:
            try:
                self._ns.__exit__(*exc)
            finally:
                self._ns = None
        if self._tm is not None:
            try:
                self._tm.__exit__(*exc)
            finally:
                self._tm = None


def annotate(name: str, **stats) -> _Annotation:
    """Name a region for profiling::

        with monitor.annotate("chainermn.decode"):
            ...   # host call OR traced computation

    Inside a trace the enclosed ops get ``name`` in their HLO metadata
    (named_scope); around a host call the region appears on the host
    timeline (TraceAnnotation), with ``stats`` as the event's arguments.
    """
    return _Annotation(str(name), stats)


__all__ = ["annotate"]
