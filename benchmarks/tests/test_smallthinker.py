"""The configuration ``smallthinker-21b-a3b-l8`` through the harness at a size
a CPU holds (window 16, prompts to 30, contexts to 48; the small
configuration borrows the published one's reference by its ``reference``
key): a sound run comes out correct, a token altered where it is produced
and the float8 control do not, and the family's counts and its classifier of
traced operations say what their docstrings say.

The small cell's limits (``data/tiny-short-long.json``) were set as the
cell's own: ``served_logit_gap`` above what sound runs read on eight seeds
and below what the control and the fault read, at these sizes on the CPU
(readings in the assertion messages' neighbourhood: sound 0.03-0.2, control
1.1-2.4, altered token 3.5 and more).
"""

import json
import os
import time
import types

import pytest

import run as benchrun
from harness import common, correct, families, peaks, weights
from test_benchmark import alter_a_token

DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = {"name": "st21b-l8-serve-short-long", "config": "tiny",
        "traffic": "tiny", "chips": 1}


def load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def drive(seed, tamper=None, seconds=1.5):
    peaks.PEAKS.setdefault("cpu", {"flops_per_s": 1e12, "bytes_per_s": 1e11})
    device = common.require_chips(1, allow_cpu=True)
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
    return benchrun.measure(CELL, load("tiny-smallthinker.json"),
                            load("tiny-short-long.json"), args, device,
                            tamper=tamper)


def test_sound_run_is_correct():
    out, checks = drive(seed=2**31 + 7)
    assert out["correct"], checks
    assert out["failed"] == 0 and out["attempted"] > 6
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0


def test_altered_token_is_not_correct():
    out, checks = drive(seed=11, tamper=alter_a_token)
    assert not out["correct"]
    assert [n for n, c in checks.items() if not c["ok"]] == [
        "served_logit_gap"]


@pytest.mark.parametrize("seed", [2, 4, 8])
def test_control_fails_serving(seed):
    from harness import serve

    cfg, tr = load("tiny-smallthinker.json"), load("tiny-short-long.json")
    args = types.SimpleNamespace(seed=seed, seconds=1.5, trace=0)
    run_rec, _, checks = serve.run(
        CELL, cfg, tr, args, common.require_chips(1, allow_cpu=True),
        time.perf_counter())
    assert all(c["ok"] for c in checks.values()), checks
    params = weights.make_tree(
        families.init_shapes(cfg, families.build_model(cfg)), seed,
        families.param_dtype(cfg))
    control = correct.check_served(cfg, tr, params, run_rec, seed, lowp=True)
    assert not control["served_logit_gap"]["ok"], control


def test_down_projections_are_drawn_as_a_kernel():
    """The family marks the experts' down projections: the harness draws
    them by its rule for kernels (1/sqrt(8 x 32) here, 0.02 for the gate
    beside them) and hands back the model's own tree, a bare array there."""
    import numpy as np

    cfg = load("tiny-smallthinker.json")
    model = families.build_model(cfg)
    shapes = families.init_shapes(cfg, model)
    assert "params/block_0/moe/w_down/kernel" in weights.leaf_paths(shapes)
    moe = weights.make_tree(shapes, 5, "float32")["params"]["block_2"]["moe"]
    assert moe["w_down"].shape == (8, 32, 64)
    assert abs(float(np.std(moe["w_down"])) * 16 - 1) < 0.05
    assert abs(float(np.std(moe["w_gate"])) / 0.02 - 1) < 0.05


def test_counts_of_the_published_layer():
    """The arithmetic the issue states for one layer and the whole cut."""
    fam = families.of({"family": "smallthinker"})
    _, cfg, tr = common.find_cell("st21b-l8-serve-short-long")
    assert fam._attn_params(cfg) == 20_971_520
    assert 64 * fam._expert_params(cfg) == 377_487_360
    model = families.build_model(cfg)
    import jax

    n = sum(int(x.size) for x in jax.tree_util.tree_leaves(
        families.init_shapes(cfg, model)))
    assert n == 8 * 398_627_840 + 2 * 151_936 * 2560 + 2560
    assert fam.kv_row_bytes(cfg, tr["engine"]) == 1056
    assert fam.layers_kept(cfg)[0] == (0, 1, 1, 1, 0, 1, 1, 1)
    # a window layer sees at most 4096 keys, a full layer all of them
    assert fam.visible(cfg, 6000, True) == 4096
    assert fam.visible(cfg, 6000, False) == 6000
    assert fam.visible_sum_prompt(cfg, 8192, True) == (
        4096 * 4097 / 2 + 4096 * 4096)


@pytest.mark.parametrize("path,experts,rest,attention", [
    ("jit(body)/chainermn.decode/SmallThinkerLM/block_3/moe/experts/"
     "pallas_call", True, False, False),
    ("jit(body)/chainermn.decode/SmallThinkerLM/block_3/moe/route/sort",
     False, True, False),
    ("jit(body)/chainermn.decode/SmallThinkerLM/block_3/moe/combine/"
     "dot_general", False, True, False),
    ("jit(body)/chainermn.decode/SmallThinkerLM/block_0/pallas_call",
     False, False, True),
    ("jit(body)/chainermn.decode/SmallThinkerLM/block_0/scatter",
     False, False, True),
    ("jit(body)/chainermn.decode/SmallThinkerLM/block_0/add",
     False, False, False),
    ("jit(body)/chainermn.decode/SmallThinkerLM/block_0/q_proj/dot_general",
     False, False, False),
    ("jit(body)/chainermn.decode/SmallThinkerLM/lm_head/dot_general",
     False, False, False),
])
def test_traced_operations_are_classified_by_where_they_were_traced(
        path, experts, rest, attention):
    fam = families.of({"family": "smallthinker"})
    assert fam.in_moe_experts("op", path, "") is experts
    assert fam.in_moe_rest("op", path, "") is rest
    assert fam.in_block_attention("op", path, "") is attention
