"""chip_smoke.py off the chip: it must refuse the CPU, its phases must run
at toy widths on the 8-device CPU mesh with interpreted kernels (the same
functions the chip runs at full width), and the compile cache it uses must
be placeable from outside and fixed otherwise."""

import os
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from chainermn_tpu import create_communicator, utils  # noqa: E402


@pytest.fixture(scope="module")
def comm():
    return create_communicator("tpu", allreduce_grad_dtype="bfloat16")


def test_main_refuses_the_cpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert out.out == ""                      # no result line
    assert "'platform': 'cpu'" in out.err     # names what it found


def test_phase_resnet_tiny(comm):
    facts = chip_smoke.phase_resnet(comm, chip_smoke.TINY)
    assert facts["global_batch"] == chip_smoke.TINY.resnet_batch * comm.size
    assert facts["all_reduce_count"] >= 1
    assert facts["losses"][-1] < facts["losses"][0]


def test_phase_lm_then_server_tiny(comm):
    sz = chip_smoke.TINY
    facts, params = chip_smoke.phase_lm(comm, sz, on_tpu=False)
    assert facts["mosaic_calls"] == 0         # interpreted off the chip
    assert facts["collective_bytes_per_step"] > 0
    served = chip_smoke.phase_server(params, sz, on_tpu=False)
    assert served["tokens_out"] == len(sz.prompt_lens) * sz.max_new
    assert served["paged_read_rel_err"] < 2e-2


@pytest.mark.slow  # ~6s; `chip_smoke.py --rehearse` runs it too
def test_phase_server_tensor_parallel_tiny(comm):
    sz = chip_smoke.TINY
    served = chip_smoke.phase_server_tp(comm, sz, on_tpu=False)
    assert served["tokens_out"] == len(sz.prompt_lens) * sz.max_new


def test_mosaic_checks_fire_off_the_chip(comm):
    """``on_tpu=True`` on the CPU: the interpreted kernel must not pass for
    a Mosaic one."""
    with pytest.raises(AssertionError, match="Mosaic"):
        chip_smoke.check_flash_parity(chip_smoke.TINY, on_tpu=True)


def test_an_array_on_device_0_alone_fails_the_check(comm):
    import numpy as np

    everywhere = comm.bcast_data(np.ones((4,), np.float32))
    chip_smoke.check_on_every_chip("test", {"w": everywhere})
    alone = jax.device_put(np.ones((4,), np.float32), jax.devices()[0])
    with pytest.raises(AssertionError, match=r"lives on \[0\] only"):
        chip_smoke.check_on_every_chip("test", {"w": everywhere, "x": alone})


def test_cache_placed_from_outside_sets_nothing(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert utils.enable_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_default_is_one_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = utils.enable_compilation_cache()
        assert first == utils.enable_compilation_cache()
        assert first == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

