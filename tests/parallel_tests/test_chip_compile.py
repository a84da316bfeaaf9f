"""Compiles for the chip without one (the TPU's own compiler, a described
``v5e:2x2``): what interpret mode cannot see. ISSUE 32's own evidence had
lowered the INTERPRETED paged kernel and so passed a copy that Mosaic
refuses; these hold the decode layer and the scale write to the real
lowering at the served widths, and count the passes over a scale array.
ISSUE 36's relayout of every expert row into padded float32 was the chip's
tiling at work on a ``[t, k, d]`` array: the mixture layer's combine is held
to the compiled program here too, and so is the linear-attention layer:
its prefill one Mosaic kernel with no chunk's matrices in HBM, its decode
step one Mosaic kernel that passes over the state store once, in place.

One file, the topology in a fixture: only the worker that runs these tests
loads the TPU's library (the on-chip-measurement guide, section 2)."""

import contextlib
import importlib.util
import pathlib

import jax
import pytest

from chainermn_tpu import ops

SCRIPTS = pathlib.Path(__file__).resolve().parents[2] / "scripts"


def _script(name):
    spec = importlib.util.spec_from_file_location(name,
                                                  SCRIPTS / f"{name}.py")
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    return script


@contextlib.contextmanager
def _traced_as_on_the_chip():
    """The kernels traced as the chip traces them, and at the chip's
    default matmul precision, not the suite's ``highest``."""
    ops.set_kernels_interpreted(False)
    try:
        with jax.default_matmul_precision("default"):
            yield
    finally:
        ops.set_kernels_interpreted(None)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to hold
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def count(topo):
    """``scripts/aot_decode_writes.py``'s count for one store and
    program."""
    script = _script("aot_decode_writes")
    with _traced_as_on_the_chip():
        yield lambda store, program: next(script.records(
            topo, [s for s in script.STORES if s[0] == store],
            (program,)))[0]


@pytest.mark.parametrize("store", ["cell1", "cell3_window", "cell5_full"])
def test_decode_layer_passes_over_no_scale_array(count, store):
    """Write and kernel at a cell's shapes, the store donated: two Mosaic
    calls (the scale write, the sweep) and not one operation, relayout or
    staging copy, over a whole scale array or int8 store. Cell 5's store has
    2 KV heads of 256: held as ``[n_blocks, bs, 2, 256]`` the chip lays it
    out tokens before heads and the scatter and the kernel's view relaid
    the whole store ten times a layer (PR 35); it is held folded."""
    rec = count(store, "decode")
    assert rec["mosaic_calls"] == 2
    assert rec["whole_scale_array_ops"] == 0
    assert rec["whole_int8_store_ops"] == 0


def test_prefill_write_relays_no_scale_array(count):
    """One row of cell 1's largest bucket: what is left over a scale array
    is the compiler's own staging copies (asynchronous, its choice program
    by program), never a relayout in the program."""
    rec = count("cell1", "prefill_write")
    assert rec["mosaic_calls"] == 1
    assert rec["whole_scale_array_ops"] == rec["async_staging"]
    assert rec["whole_int8_store_ops"] == 0


@pytest.mark.parametrize("layer", ["cell3", "cell4", "cell5"])
def test_combine_forms_no_float32_array_of_the_expert_rows(topo, layer):
    """``scripts/aot_moe_combine.py``'s listing of one mixture layer at a
    cell's widths and a decode step's rows (top-6 and top-10: neither a
    multiple of the 8 sublanes): the expert rows come back as ``top_k``
    gathers of ``[t, d]`` in bfloat16, and no operation, under
    ``moe/combine`` or under no scope at all, has a float32 result of
    ``t * k * d`` elements (the parent's ``f32[128,10,3072]`` reshape,
    25 MB written for 7.9 MB read)."""
    script = _script("aot_moe_combine")
    with _traced_as_on_the_chip():
        rec, listed = next(script.records(
            topo, [spec for spec in script.LAYERS if spec[0] == layer], ()))
    assert rec["f32_expert_row_arrays"] == 0
    assert rec["unscoped_expert_row_ops"] == 0
    rows = f"bf16[{rec['t']},{rec['d']}]"
    assert sum(o["op"] == "fusion kCustom" and o["result"].startswith(rows)
               and o["scope"].endswith("combine/gather")
               for o in listed) == rec["k"]


@pytest.mark.parametrize("program", ["256x4", "6144x1"])
def test_linear_prefill_is_one_kernel_with_no_chunk_matrices(topo, program):
    """``scripts/aot_gdn_prefill.py``'s count for one Gated DeltaNet layer
    at cell 5's widths writing into its state store, as the cell's prefill
    programs hold it (4 rows of 256, 1 row of 6144): Mosaic takes the
    kernel at heads of 128, it is the one kernel under ``gdn/recurrence``,
    and no operation has a float32 result of a chunk's ``[64, 64]``
    matrices (the XLA form's ``[.., N, 64, 64]``: 11 and 15 of them, 1.7 GB
    at 6144)."""
    script = _script("aot_gdn_prefill")
    with _traced_as_on_the_chip():
        rec, _ = next(script.records(
            topo, [p for p in script.PROGRAMS if p[0] == program]))
    assert rec["mosaic_calls"] == 1
    assert rec["chunk_arrays"] == 0


def test_linear_decode_passes_over_the_state_store_once(topo):
    """The same layer's decode program, one token for each of cell 5's 256
    slots, the store donated: Mosaic takes the kernel at heads of 128, and
    the one operation with the whole ``f32[257, 32, 128, 128]`` store as an
    operand or result is that kernel: no copy of it, no second pass (the
    XLA form's two fusions, one reading it and one reading and writing
    it)."""
    script = _script("aot_gdn_prefill")
    with _traced_as_on_the_chip():
        rec, _ = next(script.records(topo, script.DECODE, decode=True))
    assert rec["mosaic_calls"] == 1
    assert rec["store_op_kinds"] == ["custom-call"]
    assert rec["store_copies"] == 0
