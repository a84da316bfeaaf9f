"""Test harness: 8 virtual CPU devices = the reference's ``mpiexec -n 8``.

The reference tests distributed semantics with multiple MPI ranks on one box
(SURVEY.md S4). The TPU analog is a forced-CPU 8-device mesh: full collective
semantics, no TPU needed. ``chip_smoke.py``, ``benchmarks/run.py`` and
``__graft_entry__.py`` do NOT do this — they must see the real chip. The config update below forces the CPU
even on a machine that holds a chip.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_default_matmul_precision", "highest")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def n_devices():
    return len(jax.devices())
