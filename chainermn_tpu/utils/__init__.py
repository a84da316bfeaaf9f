"""Small host-side utilities (no reference counterpart; the reference leans
on mpi4py/chainer for these)."""

from __future__ import annotations

import os

# The persistent compile cache of a checkout that was given no other place
# (git-ignored). The directory is part of every cache key's lookup, so it is
# one fixed path per checkout, never derived from a pid or a temp dir.
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing in code: whoever placed the cache (a machine that keeps one
    between runs) keeps control of it. Otherwise the cache goes to
    ``.jax_cache/`` at the root of the checkout. Call before the first
    compile; ``chip_smoke.py`` and the examples call this, and the benchmark's
    harness (``benchmarks/harness/common.py``) places the same directory by
    the same rule. Nothing else names a cache directory.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE_DIR)
    return _CHECKOUT_CACHE_DIR


def ensure_batch_fits(dataset, global_batch: int, size: int = 1) -> None:
    """Fail fast when the global batch exceeds the dataset: every batch would
    be a ragged tail (which training loops skip, matching the reference's
    drop-last behavior) and zero steps would run — a silent no-op otherwise.

    ``size`` is the device count when the global batch was computed as
    per-device batch x devices (used only for the error message).
    """
    if global_batch > len(dataset):
        how = f" (= per-device batch x {size} devices)" if size > 1 else ""
        raise SystemExit(
            f"global batch {global_batch}{how} exceeds the "
            f"{len(dataset)}-sample dataset: every batch would be a ragged "
            "tail and zero training steps would run"
        )


__all__ = ["enable_compilation_cache", "ensure_batch_fits"]
