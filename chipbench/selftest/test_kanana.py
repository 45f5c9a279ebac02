"""The ``kanana_2_30b_a3b`` configuration and its cell, checked on the CPU:
``python -m pytest chipbench/selftest``.  Nothing here measures anything.

The manifest's new entries and the configuration file against the source
config, the loop and the comparison with the plain reference at a small
size, ``flops/kanana.py`` against the issue's arithmetic, and the five new
readers on a trimmed recording of a traced run on the chip."""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from chipbench import manifest as mf  # noqa: E402
from chipbench import xplane  # noqa: E402

CELL = "kanana_2_30b_a3b_train_seq4096_bs4"
NEW_METRICS = ("mla_flash_roofline", "moe_experts_roofline",
               "moe_route_ms_per_step", "moe_expert_load_max_over_mean",
               "optimizer_ms_per_step")

# https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601 config.json
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 128256}


def test_the_new_entries_validate_and_the_cell_reports_every_metric():
    manifest = mf.validate(mf.load_manifest())
    cell = mf.load_cell(CELL, manifest)
    assert cell["workload"]["chips"] == 1
    assert cell["traffic"]["driver"] == "train_loop"
    assert cell["traffic"]["batch"] * cell["traffic"]["seq_len"] == 16384
    reported = mf.metrics_of(manifest, "per_layer", CELL)
    unlisted = [m["name"] for m in manifest["per_layer"]
                if "workloads" not in m]
    assert set(unlisted) | set(NEW_METRICS) == set(reported)
    for name in NEW_METRICS:
        assert manifest["per_layer"][[m["name"] for m in manifest[
            "per_layer"]].index(name)]["workloads"] == [CELL]
        assert callable(mf.load_by_name("layer_metrics", name).read)
    assert set(mf.metrics_of(manifest, "end_to_end", CELL)) == {
        "train_examples_per_s", "step_ms_p95", "setup_s"}


def test_the_configuration_is_the_source_config_but_for_what_it_lists():
    config = mf.load_cell(CELL)["config"]
    entry = [c for c in mf.load_manifest()["configs"]
             if c["name"] == "kanana_2_30b_a3b"][0]
    assert config["source"] == entry["source"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    differs = {k for k, v in PUBLISHED.items() if config[k] != v}
    assert differs == set(config["reduced"])
    assert config["published"] == {k: PUBLISHED[k] for k in differs}
    share = config["deployment"]
    assert share["chips_sharing_a_layer"] * config["n_routed_experts"] == \
        share["n_routed_experts_routed_over"] == PUBLISHED["n_routed_experts"]
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    # the guide's floors: four expert layers, 8 experts, 1/8 vocabulary
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8 and config["assumed"]


def test_flops_reproduce_the_issue_counts():
    """ISSUE 29: attention 26.35M; dense layer 64.1M; expert layer 111.6M;
    embedding and head 65.7M; 576M.  Forward MFLOP a token at T = 4096:
    MLA projections 52.7 a layer, causal scores 41.9, dense MLP 75.5,
    shared experts 18.9, routed experts 7.1 (0.75 rows a token), head
    65.7; 2.16 GFLOP a token for training, 8.85 TFLOP an example."""
    cell = mf.load_cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    flops = mf.load_by_name("flops", "kanana")
    parts = flops.parameter_count(config)
    assert parts["attention_a_layer"] == 2048 * 6144 + 2048 * 576 \
        + 512 * 8192 + 4096 * 2048 == 26345472
    assert parts["dense_layer"] == 26345472 + 3 * 2048 * 6144
    assert round(parts["expert_layer"] / 1e6, 1) == 111.5
    assert parts["embedding_and_head"] == 2 * 16032 * 2048
    assert round(parts["total"] / 1e6) == 576
    assert parts["total"] + 25600 == config["deployment"]["parameters_held"]
    assert flops.expected_rows_per_token(config) == 0.75
    fwd = flops.forward_flops_per_token(config, 4096)
    layers = config["num_hidden_layers"]
    mega = {k: round(v / 1e6, 1) for k, v in fwd.items()}
    assert mega["mla_projections"] / layers == pytest.approx(52.7, abs=0.01)
    assert mega["causal_scores"] / layers == pytest.approx(41.9, abs=0.05)
    assert mega["dense_mlp"] == 75.5 and mega["head"] == 65.7
    assert mega["shared_experts"] / 4 == pytest.approx(18.9, abs=0.03)
    assert mega["routed_experts"] / 4 == pytest.approx(7.1, abs=0.03)
    # never top_k rows a token: that is eight chips' work
    assert fwd["routed_experts"] * 8 == pytest.approx(
        flops.forward_flops_per_token(config, 4096, 6.0)["routed_experts"])
    with pytest.raises(RuntimeError):          # no counters: no guess
        flops.rows_per_token(config, {})
    per_example = flops.train_flops_per_example(
        config, traffic, flops.expected_rows_per_token(config))
    assert per_example / 4096 == pytest.approx(2.16e9, rel=2e-3)
    assert per_example == pytest.approx(8.85e12, rel=2e-3)
    # the kernels' own counts, by hand: 3 (192 + 128) T^2 a head, causal
    assert flops.flash_flops_per_step(config, traffic) == \
        5 * 4 * 32 * 3 * 320 * 4096 * 4096
    assert flops.flash_bytes_per_step(config, traffic) == \
        5 * 4 * 32 * 4096 * 6 * 320 * 2
    assert flops.experts_flops_per_step(config, 1000) == \
        1000 * 9 * 2 * 2048 * 768
    assert flops.experts_bytes_per_step(config, 0, 4 * 16) == \
        9 * 4 * 16 * 2048 * 768 * 2
    assert flops.experts_bytes_per_step(config, 10, 0) == \
        9 * 10 * (2048 + 768) * 2


# -- the loop and the plain reference at a small size -------------------------

KANANA_TINY = {
    "builder": "kanana", "flops": "kanana", "vocab_size": 256,
    "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "q_lora_rank": None, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000.0,
    "rope_interleave": True, "rms_norm_eps": 1e-6,
    "first_k_dense_replace": 1, "intermediate_size": 128,
    "moe_intermediate_size": 32, "n_routed_experts": 2,
    "n_shared_experts": 2, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.448, "norm_topk_prob": True,
    "initializer_range": 0.02, "router_bias_std": 0.01,
    "router_bias_from_batch": True,
    "deployment": {"n_routed_experts_routed_over": 8, "first_expert": 2},
    "training": {"learning_rate": 1e-3},
    "reference": {"module": "kanana", "examples": 2, "tolerance": {
        "logits": {"rel_l2": 0.03}, "loss": {"abs": 0.05},
        "route_gates": {"rel_l2": 1e-4}}},
    "loss_band_first_step": [5.3, 5.8]}
KANANA_TINY_TRAFFIC = {"driver": "train_loop", "seq_len": 32, "batch": 2, "ring": 2,
                "offsets": 3, "warmup_steps": 1, "trace_steps": 4,
                "zipf_exponent": 1.1}


def test_drive_and_verify_at_a_small_size():
    """``drive`` trains the decoder (bf16 AMP, recompute) and ``verify``
    holds its test-mode forward to ``reference/kanana.py`` on the weights
    the window left; the counters say what the flops count by."""
    import jax

    import paddle_tpu as fluid

    driver = mf.load_by_name("traffic", "train_loop")
    cell = {"config": KANANA_TINY, "traffic": KANANA_TINY_TRAFFIC, "workload": {
        "name": "tiny", "chips": 1, "require_kernels": [],
        "require_collectives": False, "program": {"recompute": True}}}
    flops = mf.load_by_name("flops", "kanana")
    with fluid.scope_guard(fluid.Scope()):
        state = driver.drive(cell, 2800000123 % (2 ** 31 - 1), 0.5)
        assert driver.verify(state, cell, jax.devices()) == []
        counted = flops.counted_rows(KANANA_TINY)
        rows = flops.rows_per_token(KANANA_TINY, counted)
    assert not state["failed"] and state["compiles_in_window"] == 0
    found = state["report"]["reference"]
    assert 0 < found["logits_rel_l2"] < 0.03 and found["loss_abs"] < 0.05
    assert found["route_gates_rel_l2"] < 1e-4
    assert sorted(counted) == ["1", "2"]
    steps = 2 + state["steps"] + 2          # compile, warm-up, in flight
    assert {c["steps"] for c in counted.values()} == {steps}
    # the loop read them where the window opened: after the step that
    # compiled and the one that warmed up
    at_window = state["counters"]["window"]
    assert {c["steps"] for c in at_window.values()} == {2}
    inside = flops.counted_between(at_window, counted)
    assert {c["steps"] for c in inside.values()} == {state["steps"] + 2}
    assert all(sum(c["rows"]) == sum(counted[k]["rows"])
               - sum(at_window[k]["rows"]) for k, c in inside.items())
    assert 0 < rows <= 2 and rows == pytest.approx(
        2 * sum(sum(c["rows"]) for c in counted.values())
        / sum(c["possible"] for c in counted.values()))
    pools = state["pools"]
    assert (pools[0]["labels"][:, :-1] == pools[0]["input_ids"][:, 1:]).all()
    assert pools[0]["input_ids"].max() < 256


def test_route_gates_sees_the_routers_own_precision(monkeypatch):
    """``route_gates`` compares the first expert layer's gates with the
    reference's for the very rows the program's router was given, so only
    the router's own arithmetic lies between them: nothing at float32,
    and something as soon as the AMP rewrite rounds the router's weights
    to bfloat16 (``moe_route`` off the black list).  At this size (64
    tokens, 8 experts) no choice is near enough a tie to flip; at the
    cell's size a flipped choice moves a token's gates by a third, and
    the configuration file's limit lies between the two readings made
    there."""
    import paddle_tpu as fluid
    from paddle_tpu.contrib.mixed_precision import fp16_lists

    driver = mf.load_by_name("traffic", "train_loop")
    cell = {"config": KANANA_TINY, "traffic": KANANA_TINY_TRAFFIC,
            "workload": {"program": {"recompute": True}}}
    found = {}
    for router in ("float32", "bfloat16"):
        if router == "bfloat16":
            monkeypatch.setattr(fp16_lists, "black_list",
                                fp16_lists.black_list - {"moe_route"})
            monkeypatch.setattr(fp16_lists, "white_list",
                                fp16_lists.white_list | {"moe_route"})
        with fluid.scope_guard(fluid.Scope()):
            state = driver.drive(cell, 7, 0.2)
            _, measured = driver.compare_with_reference(
                state["exe"], state["builder"], cell, state["pools"])
        found[router] = measured["route_gates_rel_l2"]
    assert found["float32"] < 1e-6 < 1e-5 < found["bfloat16"], found


# -- the new readers on a recorded trace ----------------------------------------

def _recorded():
    with open(os.path.join(BENCH, "testdata", "kanana_trimmed.json")) as f:
        return json.load(f)


def _ctx(recorded, counted=True):
    """The counters as that run's readers took them: the whole run's
    totals (``recorded``: PR 29, before the loop read them where its
    windows open), here for the measured window's and for the traced
    window's alike."""
    cell = mf.load_cell(CELL)
    flops = mf.load_by_name("flops", cell["config"]["flops"])
    totals = recorded["counted_rows"] if counted else {}
    twice = {k: {"rows": [2 * r for r in c["rows"]],
                 "possible": 2 * c["possible"], "steps": 2 * c["steps"]}
             for k, c in totals.items()}
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    trace = xplane.reduce_trace(recorded, set(recorded["kernels"]))
    return {"cell": cell, "trace": trace, "peaks": peaks, "flops": flops,
            "state": {"counters": {"window": {}, "trace": totals,
                                   "end": twice}}}


def test_the_new_readers_read_the_recorded_trace():
    """``kanana_trimmed.json`` (PR 29) keeps each op's tag as that PR's
    reader worked it out, the forward pass's only; the arithmetic of the
    readers is what this holds.  The tags of every pass are held on
    ``kanana_passes_trimmed.json``, further down."""
    recorded = _recorded()
    ctx = _ctx(recorded)
    assert ctx["trace"]["steps"] == 3
    values = {name: mf.load_by_name("layer_metrics", name).read(ctx)
              for name in NEW_METRICS}
    assert all(isinstance(v, float) for v in values.values()), values
    # the file keeps a few ops of each kind a step, so a share of a
    # roofline reads high here; the arithmetic is what is held
    kernel_s, tag_s = ctx["trace"]["kernel_s"], ctx["trace"]["tag_s"]
    flash = sum(s for k, s in kernel_s.items() if "flash_attention_" in k)
    assert values["mla_flash_roofline"] == pytest.approx(
        100 * 5 * 4 * 32 * 3 * 320 * 4096 ** 2 / 197e12 / flash)
    assert values["optimizer_ms_per_step"] == pytest.approx(
        1e3 * tag_s["adam"])
    assert values["moe_route_ms_per_step"] == pytest.approx(1e3 * sum(
        tag_s[t] for t in ("moe_route", "moe_experts.dispatch",
                           "moe_experts.combine")))
    counted = recorded["counted_rows"].values()
    assert values["moe_expert_load_max_over_mean"] == pytest.approx(
        sum(max(c["rows"]) * 16 / sum(c["rows"]) for c in counted) / 4)
    assert 1.0 <= values["moe_expert_load_max_over_mean"] < 16.0
    rows = sum(sum(c["rows"]) / c["steps"] for c in counted)
    ragged = sum(s for k, s in kernel_s.items() if "ragged-dot" in k)
    assert values["moe_experts_roofline"] == pytest.approx(
        100 * rows * 18 * 2048 * 768 / 197e12 / ragged)
    # 0.85 rows a token and layer were routed here in that run, 0.75 on
    # an even spread
    assert rows / 4 / 16384 == pytest.approx(0.75, abs=0.15)
    assert values["moe_expert_load_max_over_mean"] < 4.0


def _recorded_passes():
    """``kanana_passes_trimmed.json`` (PR 35): ops carry an index into the
    step's ``op_name`` strings as compiled; the tag is worked out here,
    as ``xplane.read`` does it."""
    with open(os.path.join(BENCH, "testdata",
                           "kanana_passes_trimmed.json")) as f:
        recorded = json.load(f)
    for dev in recorded["devices"].values():
        dev["ops"] = [[name, start, dur, recorded["op_names"][i]]
                      for name, start, dur, i in dev["ops"]]
    return recorded


def test_the_backward_pass_reads_under_its_own_tags_on_a_recorded_trace():
    """Every layer of the step is a ``recompute()`` region, so jax names
    the re-run ``jvp(pd..)`` and the backward ``transpose(jvp(pd..))``.
    Until PR 35 both read ``recompute_block_grad`` and
    ``moe_route_ms_per_step`` the forward pass alone: 0.25915 ms a step in
    this file's few ops (whole trace: 19.465); now every pass, 0.45685
    (whole trace: 28.869).  Why the expected value moved: the yardstick,
    not the program.  Both numbers are counted again below straight from
    the ``op_name`` strings."""
    recorded = _recorded_passes()
    (dev,) = recorded["devices"].values()
    lo, hi, steps = xplane.steady_window(dev)
    assert steps == 2
    inside = [o for o in dev["ops"] if o[1] >= lo and o[1] + o[2] <= hi]
    loops = [o for o in inside if o[0].startswith("while")]
    assert len(loops) == 2 * 8         # 4 layers: forward, and backward
    assert all(o[3].endswith("_moe_experts)/while")
               or o[3].endswith("_moe_experts/while") for o in loops)

    def spent(pattern):     # ms a step, by the op_name's letters alone
        import re
        return sum(o[2] for o in inside if o not in loops
                   and re.search(pattern, o[3])) / 1e6 / steps

    tagged = [dict(d, ops=[[n, s, t, xplane.program_op(op_name)]
                           for n, s, t, op_name in d["ops"]])
              for d in recorded["devices"].values()]
    trace = dict(recorded, devices={"/device:TPU:0": tagged[0]})
    out = xplane.reduce_trace(trace, set(recorded["kernels"]))
    ctx = {"trace": out}
    route = mf.load_by_name("layer_metrics", "moe_route_ms_per_step")
    every_pass = spent(r"_moe_route\)*/|\.(dispatch|combine)\)*/")
    forward = spent(r"/pd\d+_moe_route/|/pd\d+_moe_experts\."
                    r"(dispatch|combine)/")
    assert route.read(ctx) == pytest.approx(every_pass)
    assert every_pass == pytest.approx(0.4568505, abs=1e-7)
    assert forward == pytest.approx(0.25915, abs=1e-7)
    tag_s = out["tag_s"]
    # the backward's parts are named, the program's slip of naming a
    # part after another op is put right, the region keeps what is its own
    assert {"mla_attention", "moe_shared", "dense_mlp", "lm_head",
            "moe_experts.products", "moe_route", "rms_norm"} <= set(tag_s)
    assert not any(t.startswith("elementwise_add.") for t in tag_s)
    assert tag_s["recompute_block_grad"] == pytest.approx(
        spent(r"recompute_block_grad/optimization_barrier$") / 1e3)
    # the loops are counted by their bodies: nothing twice
    assert sum(out["category_s"].values()) <= out["busy_s"] / steps
    assert sum(out["category_s"].values()) + sum(
        o[2] for o in loops) / 1e9 / steps > out["busy_s"] / steps
    # the kernels' times are events of their own names: untouched
    assert out["kernel_calls"]["flash_attention_dq"] == pytest.approx(
        sum(1 for o in inside if o[0].startswith("flash_attention_dq"))
        / steps)
    assert mf.load_by_name("layer_metrics", "optimizer_ms_per_step").read(
        ctx) == pytest.approx(spent(r"/pd\d+_adam/"))


def test_the_counters_readers_read_a_windows_steps_alone():
    """Two hand-made readings of one layer's counters, 5 steps of set-up
    apart from 45 of the window, and a third after 18 traced steps.
    Set-up sent every row to expert 0; the window spread 100 rows a step
    evenly over 4 experts but for 10 more to expert 1; the traced steps
    sent 40 a step to each."""
    recorded = _recorded()
    ctx = _ctx(recorded)
    flops, config = ctx["flops"], ctx["cell"]["config"]
    possible = 16384 * 6
    window = {"1": {"rows": [5000, 0, 0, 0], "possible": 5 * possible,
                    "moved": 5 * 8192, "steps": 5}}
    trace = {"1": {"rows": [5000 + 45 * 25, 45 * 35, 45 * 25, 45 * 25],
                   "possible": 50 * possible, "moved": 50 * 8192,
                   "steps": 50}}
    end = {"1": {"rows": [r + 18 * 40 for r in trace["1"]["rows"]],
                 "possible": 68 * possible, "moved": 68 * 8192,
                 "steps": 68}}
    assert flops.counted_between(window, trace) == {"1": {
        "rows": [45 * 25, 45 * 35, 45 * 25, 45 * 25],
        "possible": 45 * possible, "moved": 45 * 8192, "steps": 45}}
    assert flops.counted_between({}, window) == window
    assert flops.counted_between(trace, trace) == {}      # not a step
    ctx["state"] = {"counters": {"window": window, "trace": trace,
                                 "end": end}}
    ctx["measured"] = {"train_examples_per_s": 6.0}

    def read(name):
        return mf.load_by_name("layer_metrics", name).read(ctx)

    # fullest 35 over the mean 27.5 a step; set-up's 5000 : 0 is not in it
    assert read("moe_expert_load_max_over_mean") == pytest.approx(35 / 27.5)
    # 110 rows a step of 98,304 choices: that share of top_k a token
    rows = 6 * 110 / possible
    assert flops.rows_per_token(config, flops.counted_between(
        window, trace)) == pytest.approx(rows)
    assert read("mfu") == pytest.approx(
        100 * 6.0 * flops.train_flops_per_example(
            config, ctx["cell"]["traffic"], rows) / 197e12)
    # the roofline takes the traced steps' 160 rows a step, 4 experts
    # with a row in each; the recorded kernels' time as it is
    ragged = sum(s for k, s in ctx["trace"]["kernel_s"].items()
                 if "ragged-dot" in k)
    least = max(160 * 18 * 2048 * 768 / 197e12,
                9 * (160 * (2048 + 768) + 4 * 2048 * 768) * 2 / 819e9)
    assert read("moe_experts_roofline") == pytest.approx(
        100 * least / ragged)


def test_the_new_readers_return_nothing_where_there_is_nothing_to_read():
    """A program with no such kernels, tags or counters (the parent of the
    PR that brought them): None, and no exception."""
    ctx = _ctx(_recorded(), counted=False)
    ctx["trace"] = dict(ctx["trace"], kernel_s={}, tag_s={"mul": 1e-3})
    for name in NEW_METRICS:
        assert mf.load_by_name("layer_metrics", name).read(ctx) is None
    bert = mf.load_by_name("flops", "bert")
    for name in NEW_METRICS:
        assert mf.load_by_name("layer_metrics", name).read(
            dict(ctx, flops=bert)) is None
