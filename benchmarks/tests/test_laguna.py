"""The configuration ``laguna-s-2.1-l5-ep2`` through the harness at a size a
CPU holds (window 16, prompts to 30, contexts to 48, 4 of 8 experts held;
the small configuration borrows the published one's reference by its
``reference`` key): a sound run comes out correct, a token altered where it
is produced and the float8 control do not, the issue's parameter arithmetic
comes out of the configuration's file, and the family's classifier of traced
operations says what its docstrings say.

The small cell's limits (``data/tiny-short-long-w.json``) were set as the
cell's own: ``served_logit_gap`` 0.5 lies above what sound runs read on ten
seeds (0.043-0.220) and below what the float8 control read on them
(1.19-3.32), at these sizes on the CPU; ``slots_held_share`` read
0.887-0.908, its lower limit is 0.8 as in the other small closed loops.
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
NAME = "laguna-l5-ep2-serve-short-long"
CELL = {"name": NAME, "config": "tiny", "traffic": "tiny", "chips": 1}


def load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def drive(seed, tamper=None, seconds=1.5):
    peaks.PEAKS.setdefault("cpu", {"flops_per_s": 1e12, "bytes_per_s": 1e11})
    device = common.require_chips(1, allow_cpu=True)
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
    return benchrun.measure(CELL, load("tiny-laguna.json"),
                            load("tiny-short-long-w.json"), args, device,
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

    cfg, tr = load("tiny-laguna.json"), load("tiny-short-long-w.json")
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


def test_the_issues_parameter_arithmetic():
    """From the configuration's file and from the model's own shapes."""
    import jax

    fam = families.of({"family": "laguna"})
    _, cfg, tr = common.find_cell(NAME)
    assert fam.attn_params(cfg, 48) == 44_187_648
    assert fam.attn_params(cfg, 72) == 63_135_744
    assert fam.expert_params(cfg) == 9_437_184
    assert [fam.layer_params(cfg, i) for i in range(5)] == [
        157_440_000, 1_281_325_056, 1_281_325_056, 1_281_325_056,
        1_262_376_960]
    assert fam.total_params(cfg) == 5_572_076_544
    shapes = families.init_shapes(cfg, families.build_model(cfg))
    assert sum(int(x.size) for x in jax.tree_util.tree_leaves(
        shapes)) == 5_572_076_544
    moe = shapes["params"]["block_1"]["moe"]
    assert moe["router"].shape == (3072, 256)        # the published width
    assert moe["w_gate"].shape == (128, 3072, 1024)  # the experts held
    assert fam._st.kv_row_bytes(cfg, tr["engine"]) == 2112
    assert fam.layers_kept(cfg) == [
        (False, 48, True), (True, 72, False), (True, 72, False),
        (True, 72, False), (False, 48, False)]
    assert fam.held_share(cfg) == 0.5
    assert fam.visible(cfg, 6000, True) == 512
    assert fam.visible_sum_prompt(cfg, 1024, True) == (
        512 * 513 / 2 + 512 * 512)
    # a token's FLOPs: what it meets, 5 of its 10 routed experts expected
    assert fam.routed_flops_per_token(cfg) == 2 * 5 * 9_437_184


def test_the_file_states_the_cut():
    _, cfg, tr = common.find_cell(NAME)
    pub = cfg["published"]
    for key, value in pub.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert cfg["reduced_from"] == {k: pub[k] for k in cfg["reduced"]} == {
        "num_hidden_layers": 48, "num_experts": 256, "vocab_size": 100352}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (5, 128, 50176)
    assert cfg["held_experts"] == {"first": 0, "count": 128,
                                   "published": 256}
    assert len(cfg["assumed"]) >= 9 and "2 chips" in cfg["deployment"]
    bj = common.benchmark_json()
    entry = [c for c in bj["configs"] if c["name"] == "laguna-s-2.1-l5-ep2"]
    assert entry[0]["source"] == cfg["source"] and entry[0][
        "reduced"] == cfg["reduced"]
    eng = tr["engine"]
    ring = -(-(cfg["sliding_window"] + eng["kv_block_size"])
             // eng["kv_block_size"])
    assert eng["kv_window_blocks"] >= eng["n_slots"] * ring + 1


def test_the_cell_reports_its_metrics_through_files_that_exist():
    per_layer = {m["name"] for m in common.metric_entries(NAME, "per_layer")}
    assert len(per_layer) == 19
    assert {"moe_shared_ms_per_step.shortlong", "moe_roofline.shortlong",
            "step_mfu.shortlong"} <= per_layer
    assert not {"step_mfu.decode", "paged_decode_roofline.decode",
                "kv_pool_live_share.decode"} & per_layer
    for name in per_layer:
        assert os.path.exists(os.path.join(
            common.BENCH_DIR, "metrics", name + ".py")), name
    fam = families.of({"family": "laguna"})
    for reader in ("step_mfu_pct", "moe_roofline_pct",
                   "moe_dispatch_ms_per_step", "moe_shared_ms_per_step",
                   "paged_decode_roofline_pct",
                   "prefill_attention_roofline_pct",
                   "kv_pool_live_share_pct"):
        assert callable(getattr(fam, reader))
    # the new metric's file reads nothing for a family without the reader
    shared = common.load_module("metrics",
                                "moe_shared_ms_per_step.shortlong.py")
    assert shared.read({"config": {"family": "smallthinker"}}) is None


PRE = "jit(body)/chainermn.decode/LagunaLM/"


@pytest.mark.parametrize("path,kind", [
    (PRE + "block_3/moe/experts/pallas_call", "experts"),
    (PRE + "block_3/moe/shared/gate_proj/dot_general", "shared"),
    (PRE + "block_3/moe/shared/mul", "shared"),
    (PRE + "block_3/moe/route/sort", "rest"),
    (PRE + "block_3/moe/combine/dot_general", "rest"),
    (PRE + "block_0/mlp/down_proj/dot_general", "mlp"),
    (PRE + "block_0/pallas_call", "attention"),
    (PRE + "block_0/scatter", "attention"),
    (PRE + "block_0/logistic", "attention"),
    (PRE + "block_0/add", None),
    (PRE + "block_0/g_proj/dot_general", None),
    (PRE + "block_0/q_proj/dot_general", None),
    (PRE + "lm_head/dot_general", None),
])
def test_traced_operations_are_classified_by_where_they_were_traced(path,
                                                                    kind):
    fam = families.of({"family": "laguna"})
    got = {"experts": fam.in_moe_experts, "shared": fam.in_moe_shared,
           "rest": fam.in_moe_rest, "mlp": fam.in_dense_mlp,
           "attention": fam.in_block_attention}
    assert [k for k, f in got.items() if f("op", path, "")] == (
        [kind] if kind else [])


class _Trace:
    """A reduced trace written by hand: one decode span and one prefill span
    on the harness's clock, a few device operations inside each."""

    window_s, busy_s, begin, end = 1.0, 0.5, 0.0, 1.0

    def __init__(self):
        self.host = {"chainermn.serving_decode": [(0.10, 0.20)],
                     "chainermn.serving_prefill": [(0.30, 0.50)]}
        op = lambda path, a, b: ("op", PRE.replace(
            "chainermn.decode/", "") + path, "", a, b)
        self.ops = [
            op("block_1/moe/experts/pallas_call", 0.10, 0.13),
            op("block_1/moe/shared/up_proj/dot_general", 0.13, 0.134),
            op("block_1/moe/route/sort", 0.134, 0.135),
            op("block_1/pallas_call", 0.14, 0.15),
            op("block_1/moe/experts/pallas_call", 0.30, 0.36),
            op("block_1/moe/shared/up_proj/dot_general", 0.36, 0.37),
            op("block_4/pallas_call", 0.40, 0.45)]

    def to_perf(self, t):
        return t

    def spans(self, name):
        return self.host.get(name, [])

    def ops_between(self, pick, device=0):
        return [(a, b) for n, p, c, a, b in self.ops if pick(n, p, c)]

    def op_seconds(self, pick, device=None):
        return sum(b - a for a, b in self.ops_between(pick))


def test_readers_on_a_trace_counted_by_hand():
    """One decode step of 2 tokens (contexts 701 and 101) and one prefill of
    600 tokens in the traced second, at the published sizes."""
    fam = families.of({"family": "laguna"})
    _, cfg, tr = common.find_cell(NAME)
    req = lambda p, stamps: types.SimpleNamespace(
        prompt=[0] * p, stamps=stamps, max_new=4)
    run = {"config": cfg, "traffic": tr, "trace": _Trace(), "t0": 0.0,
           "t1": 1.0, "seconds": 1.0, "device": {"kind": "TPU v5 lite"},
           "requests": [req(700, [-1.0, 0.21]), req(100, [-0.5, 0.22]),
                        req(600, [0.51])]}
    assert fam.moe_shared_ms_per_step(run) == pytest.approx(4.0)
    assert fam.moe_dispatch_ms_per_step(run) == pytest.approx(1.0)
    # two programs read the 128 experts held in 4 layers: 2 x 9.66 GB over
    # 819 GB/s is more than the FLOPs of 602 tokens x 5 experts ask for
    least = 2 * 4 * 128 * 9_437_184 * 2 / 819e9
    assert fam.moe_roofline_pct(run) == pytest.approx(100 * least / 0.09)
    # decode attention: bytes bind; full layers see 701 + 101 keys, window
    # layers 512 + 101
    kv = 2112 * (2 * 802 + 3 * 613)
    qo = 2 * 2.0 * (2 * 48 + 3 * 72) * 128 * 2
    assert fam.paged_decode_roofline_pct(run) == pytest.approx(
        100 * (kv + qo) / 819e9 / 0.01)
    pairs_full, pairs_win = 600 * 601 / 2, 512 * 513 / 2 + 88 * 512
    flops = 4 * 128 * (2 * 48 * pairs_full + 3 * 72 * pairs_win)
    assert fam.prefill_attention_roofline_pct(run) == pytest.approx(
        100 * flops / 197e12 / 0.05)
    assert 0 < fam.step_mfu_pct(run) < 100
    assert 0 < fam.kv_pool_live_share_pct(run) < 100
    for entry in common.metric_entries(NAME, "per_layer"):
        if entry["name"].endswith(".shortlong"):
            assert common.load_module(
                "metrics", entry["name"] + ".py").read(run) is not None
