"""The configuration ``qwen3-next-80b-a3b-l4-ep2`` through the harness at a
size a CPU holds (three linear-attention layers of 16 value heads on 8 key
heads of 8 and one full layer of 8 query heads on 1 KV head of 16, 4 of 8
experts held, prompts to 30, contexts to 48; the small configuration borrows
the published one's reference by its ``reference`` key): a sound run comes
out correct, a decode that starts from a zero state (what prefill wrote is
not carried) and the float8 control do not, the issue's parameter arithmetic
comes out of the configuration's file and of the model's own shapes, and the
family's readers say of a trace written by hand what its docstrings say.

The small cell's limits (``data/tiny-short-long-s.json``) were set as the
cell's own, at these sizes on the CPU, the experts' down projections drawn
as a kernel as at full size: ``served_logit_gap`` 1.0 lies above what sound
runs read (0.10-0.18 on four seeds) and below what the float8 control reads
(4.37-5.07 on them). The small configuration's ``rms_norm_eps`` is 1e-3, not
the published 1e-6: the gated norm after the recurrence divides a head's
output by its own size, and at 8-wide heads that output is now and then
small enough for bfloat16's rounding to turn it (at 1e-6 six seeds read
0.25-1.41); 1e-3 stands to such an output as 1e-6 does at heads of 128
(PERF.md section 7, From PR 35). ``slots_held_share`` read 0.82-0.85, its
lower limit is 0.8 as in the other small closed loops.
"""

import json
import os
import time
import types

import pytest

import run as benchrun
from harness import common, correct, families, peaks, weights

DATA = os.path.join(os.path.dirname(__file__), "data")
NAME = "qwen3next-l4-ep2-serve-short-long"
CELL = {"name": NAME, "config": "tiny", "traffic": "tiny", "chips": 1}
FAM = {"family": "qwen3_next"}


def load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def drive(seed, tamper=None, seconds=1.5):
    peaks.PEAKS.setdefault("cpu", {"flops_per_s": 1e12, "bytes_per_s": 1e11})
    device = common.require_chips(1, allow_cpu=True)
    args = types.SimpleNamespace(seed=seed, seconds=seconds, trace=0)
    return benchrun.measure(CELL, load("tiny-qwen3-next.json"),
                            load("tiny-short-long-s.json"), args, device,
                            tamper=tamper)


def test_sound_run_is_correct():
    out, checks = drive(seed=2**31 + 7)
    assert out["correct"], checks
    assert out["failed"] == 0 and out["attempted"] > 6
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0


def forget_the_state(engine, client):
    """The planted fault: once a prompt is prefilled, the rows its slot got
    in the state store are zeroed, so decode starts from nothing."""
    admit = engine._paged_admit
    linear = [i for st in engine._state for i in st.kind.layers]

    def admit_and_forget(plans, **kw):
        out = admit(plans, **kw)
        for i in linear:
            engine._store[i] = {
                key: x.at[[slot for slot, _ in out]].set(0)
                for key, x in engine._store[i].items()}
        return out

    engine._paged_admit = admit_and_forget


def test_decode_from_a_zero_state_is_not_correct():
    out, checks = drive(seed=11, tamper=forget_the_state)
    assert not out["correct"]
    assert not checks["served_logit_gap"]["ok"], checks
    assert checks["served_length_mismatch"]["ok"]


@pytest.mark.parametrize("seed", [2, 4, 8])
def test_control_fails_serving(seed):
    from harness import serve

    cfg, tr = load("tiny-qwen3-next.json"), load("tiny-short-long-s.json")
    args = types.SimpleNamespace(seed=seed, seconds=1.5, trace=0)
    run_rec, _, checks = serve.run(
        CELL, cfg, tr, args, common.require_chips(1, allow_cpu=True),
        time.perf_counter())
    assert all(c["ok"] for n, c in checks.items()
               if n != "served_logit_gap"), checks
    params = weights.make_tree(
        families.init_shapes(cfg, families.build_model(cfg)), seed,
        families.param_dtype(cfg))
    control = correct.check_served(cfg, tr, params, run_rec, seed, lowp=True)
    assert not control["served_logit_gap"]["ok"], control


def test_the_issues_parameter_arithmetic():
    """From the configuration's file and from the model's own shapes."""
    import jax

    fam = families.of(FAM)
    _, cfg, tr = common.find_cell(NAME)
    assert fam.gdn_params(cfg) == 33_718_464
    assert fam.attn_params(cfg) == 27_263_488
    assert fam.layer_rest_params(cfg) == 4_200_448
    assert fam.expert_params(cfg) == 3_145_728
    assert fam.linear_layers(cfg) == [0, 1, 2]
    assert fam.total_params(cfg) == 3_677_613_120 == (
        3 * 843_225_280 + 836_770_304 + 311_166_976)
    shapes = families.init_shapes(cfg, families.build_model(cfg))
    assert sum(int(x.size) for x in jax.tree_util.tree_leaves(
        shapes)) == 3_677_613_120
    block = shapes["params"]["block_0"]
    assert block["moe"]["router"].shape == (2048, 512)   # published width
    assert block["moe"]["w_gate"].shape == (256, 2048, 512)   # those held
    assert block["moe"]["shared_gate"].shape == (2048, 1)
    assert block["gdn"]["qkvz_proj"]["kernel"].shape == (2048, 12288)
    assert block["gdn"]["ba_proj"]["kernel"].shape == (2048, 64)
    assert block["gdn"]["A_log"].shape == (32,)
    assert shapes["params"]["block_3"]["attn"]["q_proj"][
        "kernel"].shape == (2048, 8192)
    # the convolution's weights are drawn as a kernel (the file's assumed)
    assert type(block["gdn"]["conv_kernel"]).__name__ == "_AsKernel"
    assert block["gdn"]["conv_kernel"].leaf.shape == (4, 8192)
    assert fam._st.kv_row_bytes(cfg, tr["engine"]) == 1040
    assert fam.state_bytes(cfg) == 2_097_152
    assert fam.conv_state_bytes(cfg) == 49_152
    assert fam.held_share(cfg) == 0.5
    # a token's FLOPs: what it meets, 5 of its 10 routed experts expected,
    # and the recurrence's own 7 dk dv a value head
    assert fam.routed_flops_per_token(cfg) == 2 * 5 * 3_145_728
    assert fam.recurrence_flops_per_token(cfg) == 7 * 32 * 128 * 128
    rest = 2 * (1_048_576 + 3_145_728 + 2_048) + 2 * 5 * 3_145_728
    assert fam.matmul_flops_per_token(cfg) == (
        3 * (2 * (33_718_464 - 64 - 128) + rest)
        + 2 * (27_263_488 - 512) + rest)
    # the state store of 256 slots and the decode step's bytes (ISSUE 35)
    eng = tr["engine"]
    store = 3 * eng["n_slots"] * (fam.state_bytes(cfg)
                                  + fam.conv_state_bytes(cfg))
    assert 1.64e9 < store < 1.66e9


def test_the_file_states_the_cut():
    _, cfg, tr = common.find_cell(NAME)
    pub = cfg["published"]
    for key, value in pub.items():
        if key not in cfg["reduced"]:
            assert cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert cfg["reduced_from"] == {k: pub[k] for k in cfg["reduced"]} == {
        "num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (4, 256, 75968)
    assert cfg["held_experts"] == {"first": 0, "count": 256,
                                   "published": 512}
    assert cfg["state_dtype"] == "float32"
    assert len(cfg["assumed"]) >= 12 and "2 chips" in cfg["deployment"]
    assert any("interleave" in d for d in cfg["departures"])
    bj = common.benchmark_json()
    entry = [c for c in bj["configs"]
             if c["name"] == "qwen3-next-80b-a3b-l4-ep2"]
    assert entry[0]["source"] == cfg["source"] and entry[0][
        "reduced"] == cfg["reduced"]
    eng = tr["engine"]
    assert "kv_window_blocks" not in eng
    assert tr["arrivals"]["clients"] == eng["n_slots"] * 5 // 4
    assert tr["lengths"]["prompt"] == load_traffic("short-and-long")[
        "lengths"]["prompt"]
    assert tr["lengths"]["answer"] == load_traffic("short-and-long")[
        "lengths"]["answer"]


def load_traffic(name):
    return common.load_json("traffic", name + ".json")


def test_the_cell_reports_its_metrics_through_files_that_exist():
    per_layer = {m["name"] for m in common.metric_entries(NAME, "per_layer")}
    assert len(per_layer) == 22
    assert {"gdn_decode_roofline.shortlong", "gdn_prefill_roofline.shortlong",
            "gdn_ms_per_step.shortlong", "moe_shared_ms_per_step.shortlong",
            "step_mfu.shortlong"} <= per_layer
    assert not {"step_mfu.decode", "paged_decode_roofline.decode",
                "kv_pool_live_share.decode"} & per_layer
    for name in per_layer:
        assert os.path.exists(os.path.join(
            common.BENCH_DIR, "metrics", name + ".py")), name
    end_to_end = {m["name"] for m in common.metric_entries(NAME,
                                                           "end_to_end")}
    assert end_to_end == {"serve_tokens_per_s", "setup_s"}
    fam = families.of(FAM)
    for reader in ("step_mfu_pct", "moe_roofline_pct",
                   "moe_dispatch_ms_per_step", "moe_shared_ms_per_step",
                   "paged_decode_roofline_pct",
                   "prefill_attention_roofline_pct",
                   "kv_pool_live_share_pct", "gdn_decode_roofline_pct",
                   "gdn_prefill_roofline_pct", "gdn_ms_per_step"):
        assert callable(getattr(fam, reader))
    # the new metrics' files read nothing for a family without the readers
    for name in ("gdn_decode_roofline", "gdn_prefill_roofline",
                 "gdn_ms_per_step"):
        metric = common.load_module("metrics", name + ".shortlong.py")
        assert metric.read({"config": {"family": "laguna"}}) is None
    # and the three new cells are the only ones that list them
    for m in common.benchmark_json()["per_layer"]:
        if m["name"].startswith("gdn_"):
            assert m["workloads"] == [NAME] and m[
                "layer"] == "linear-attention layer"


PRE = "jit(body)/chainermn.decode/Qwen3NextLM/"


@pytest.mark.parametrize("path,kind", [
    (PRE + "block_1/gdn/recurrence/mul", "recurrence"),
    (PRE + "block_1/gdn/recurrence/while/body/dot_general", "recurrence"),
    (PRE + "block_1/gdn/conv/mul", "gdn"),
    (PRE + "block_1/gdn/in_proj/qkvz_proj/dot_general", "gdn"),
    (PRE + "block_1/gdn/norm_gate/norm/mul", "gdn"),
    (PRE + "block_1/gdn/out_proj/dot_general", "gdn"),
    (PRE + "block_3/attn/pallas_call", "attention"),
    (PRE + "block_3/attn/scatter", "attention"),
    (PRE + "block_3/attn/logistic", "attention"),
    (PRE + "block_3/attn/q_proj/dot_general", None),
    (PRE + "block_3/attn/q_norm/mul", None),
    (PRE + "block_3/moe/experts/pallas_call", "experts"),
    (PRE + "block_3/moe/shared/gate_proj/dot_general", "shared"),
    (PRE + "block_3/moe/shared/gate/dot_general", "shared"),
    (PRE + "block_3/moe/route/sort", "rest"),
    (PRE + "block_3/moe/combine/dot_general", "rest"),
    (PRE + "block_0/add", None),
    (PRE + "lm_head/dot_general", None),
])
def test_traced_operations_are_classified_by_where_they_were_traced(path,
                                                                    kind):
    fam = families.of(FAM)
    got = {"experts": fam.in_moe_experts, "shared": fam.in_moe_shared,
           "rest": fam.in_moe_rest, "gdn": fam.in_gdn,
           "attention": fam.in_block_attention}
    want = {None: [], "recurrence": ["gdn"]}.get(kind, [kind])
    assert [k for k, f in got.items() if f("op", path, "")] == want
    assert fam.in_gdn_recurrence("op", path, "") == (kind == "recurrence")


class _Trace:
    """A reduced trace written by hand: one decode span and one prefill span
    on the harness's clock, a few device operations inside each; the
    prefill's recurrence is a loop and the operations of its body, which
    count once."""

    window_s, busy_s, begin, end = 1.0, 0.5, 0.0, 1.0

    def __init__(self):
        self.host = {"chainermn.serving_decode": [(0.10, 0.20)],
                     "chainermn.serving_prefill": [(0.30, 0.50)]}
        op = lambda path, a, b: ("op", PRE.replace(
            "chainermn.decode/", "") + path, "", a, b)
        self.ops = [
            op("block_0/gdn/in_proj/qkvz_proj/dot_general", 0.100, 0.101),
            op("block_0/gdn/recurrence/reduce", 0.101, 0.103),
            op("block_0/gdn/recurrence/add", 0.103, 0.106),
            op("block_0/gdn/norm_gate/mul", 0.106, 0.107),
            op("block_0/moe/experts/pallas_call", 0.11, 0.13),
            op("block_0/moe/shared/up_proj/dot_general", 0.13, 0.134),
            op("block_0/moe/route/sort", 0.134, 0.135),
            op("block_3/attn/pallas_call", 0.14, 0.15),
            op("block_0/gdn/recurrence/while", 0.30, 0.34),
            op("block_0/gdn/recurrence/while/body/dot_general", 0.30, 0.31),
            op("block_0/gdn/recurrence/while/body/dot_general", 0.32, 0.33),
            op("block_0/moe/experts/pallas_call", 0.35, 0.40),
            op("block_3/attn/pallas_call", 0.40, 0.45)]

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
    fam = families.of(FAM)
    _, cfg, tr = common.find_cell(NAME)
    req = lambda p, stamps: types.SimpleNamespace(
        prompt=[0] * p, stamps=stamps, max_new=4)
    run = {"config": cfg, "traffic": tr, "trace": _Trace(), "t0": 0.0,
           "t1": 1.0, "seconds": 1.0, "device": {"kind": "TPU v5 lite"},
           "requests": [req(700, [-1.0, 0.21]), req(100, [-0.5, 0.22]),
                        req(600, [0.51])]}
    assert fam.gdn_ms_per_step(run) == pytest.approx(7.0)
    assert fam.moe_shared_ms_per_step(run) == pytest.approx(4.0)
    assert fam.moe_dispatch_ms_per_step(run) == pytest.approx(1.0)
    # decode: 2 tokens read and write their state in 3 layers, bytes bind
    token = 4 * (2 * 16 * 128 + 2 * 32 * 128 + 2 * 32)
    need = 2 * 3 * (2 * 2_097_152 + token)
    assert fam.gdn_decode_roofline_pct(run) == pytest.approx(
        100 * need / 819e9 / 0.005)
    # prefill: 600 tokens' operands and one final state in 3 layers against
    # 7 dk dv a token a value head; the loop and its body count once (0.04)
    bytes_ = 3 * (600 * token + 2_097_152)
    flops = 600 * 3 * 7 * 32 * 128 * 128
    assert bytes_ / 819e9 > flops / 197e12
    assert fam.gdn_prefill_roofline_pct(run) == pytest.approx(
        100 * bytes_ / 819e9 / 0.04)
    # two programs read the 256 experts held in 4 layers
    least = 2 * 4 * 256 * 3_145_728 * 2 / 819e9
    assert fam.moe_roofline_pct(run) == pytest.approx(100 * least / 0.07)
    # decode attention in the one full layer: 701 + 101 keys
    kv = 1040 * 802
    qo = 2 * 3.0 * 16 * 256 * 2
    assert fam.paged_decode_roofline_pct(run) == pytest.approx(
        100 * (kv + qo) / 819e9 / 0.01)
    # prefill attention of 600 tokens: its bytes (q, gate and o in bf16, K
    # and V read once in bf16 and written once in int8) outlast its FLOPs
    flops = 4 * 16 * 256 * (600 * 601 / 2)
    moved = 600 * (3.0 * 16 * 256 * 2 + 2 * 2 * 256 * 2 + 1040)
    assert moved / 819e9 > flops / 197e12
    assert fam.prefill_attention_roofline_pct(run) == pytest.approx(
        100 * moved / 819e9 / 0.05)
    assert 0 < fam.step_mfu_pct(run) < 100
    # live: the two decoding requests' rows and three state rows each from
    # their stamps on, of a store of 256 rows and 55,400 blocks
    assert 0 < fam.kv_pool_live_share_pct(run) < 100
    for entry in common.metric_entries(NAME, "per_layer"):
        if entry["name"].endswith(".shortlong"):
            assert common.load_module(
                "metrics", entry["name"] + ".py").read(run) is not None
