"""The ``mellum2_12b_a2_5b`` configuration and its cell, checked on the CPU:
``python -m pytest chipbench/selftest``.  Nothing here measures anything.

The manifest's new entries and the configuration file against the catalog
row's config, the loop and the comparison with the plain reference at a
small size, ``flops/mellum2.py`` against ISSUE 36's arithmetic, and the
four new readers on a trimmed recording of a traced run on the chip."""

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

MELLUM2_CELL = "mellum2_12b_a2_5b_train_seq8192_bs2"
MELLUM2_METRICS = ("swa_flash_roofline", "flash_window_blocks_visited_share",
               "swa_attention_ms_per_step", "gqa_attention_ms_per_step")

PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct config.json,
# as the catalog beside the model-configs guide holds it
MELLUM2_PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}


def test_mellum2_entries_validate_and_the_cell_reports_every_metric():
    manifest = mf.validate(mf.load_manifest())
    cell = mf.load_cell(MELLUM2_CELL, manifest)
    assert cell["workload"]["chips"] == 1
    assert cell["workload"]["require_kernels"] == [
        "flash_attention_fwd", "flash_attention_dkv", "flash_attention_dq",
        "ragged-dot"]
    assert cell["traffic"] == dict(
        cell["traffic"], driver="train_loop", seq_len=8192, batch=2, ring=8,
        offsets=32, warmup_steps=4, trace_steps=8, zipf_exponent=1.1)
    reported = mf.metrics_of(manifest, "per_layer", MELLUM2_CELL)
    unlisted = [m["name"] for m in manifest["per_layer"]
                if "workloads" not in m]
    assert set(unlisted) | set(MELLUM2_METRICS) == set(reported)
    assert "dispatch_lead_ms" in reported
    # appended, in the issue's order, each for this cell alone
    assert [m["name"] for m in manifest["per_layer"][-4:]] == list(
        MELLUM2_METRICS)
    for m in manifest["per_layer"][-4:]:
        assert m["workloads"] == [MELLUM2_CELL]
        assert m["moves"] == "train_examples_per_s"
        assert callable(mf.load_by_name("layer_metrics", m["name"]).read)
    assert manifest["workloads"][-1]["name"] == MELLUM2_CELL
    assert manifest["configs"][-1]["name"] == "mellum2_12b_a2_5b"
    assert set(mf.metrics_of(manifest, "end_to_end", MELLUM2_CELL)) == {
        "train_examples_per_s", "step_ms_p95", "setup_s"}


def test_mellum2_configuration_is_the_catalog_row_but_for_what_it_lists():
    config = mf.load_cell(MELLUM2_CELL)["config"]
    entry = mf.load_manifest()["configs"][-1]
    assert config["source"] == entry["source"] == (
        "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/"
        "main/config.json")
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    differs = {k for k, v in MELLUM2_PUBLISHED.items() if config[k] != v}
    assert differs == set(config["reduced"])
    assert config["published"] == {k: MELLUM2_PUBLISHED[k] for k in differs}
    share = config["deployment"]
    assert share["chips_sharing_a_layer"] * config["num_experts"] == \
        share["num_experts_routed_over"] == MELLUM2_PUBLISHED["num_experts"]
    assert config["vocab_size"] * 8 == MELLUM2_PUBLISHED["vocab_size"]
    # the guide's floors: whole periods and four layers, 8 experts, 1/8
    assert config["num_hidden_layers"] % 4 == 0 \
        and config["num_hidden_layers"] >= 4
    assert config["num_experts"] >= 8 and len(config["assumed"]) >= 8
    # what the builder hands models/decoder.py: the published widths
    built = mf.load_by_name("builders", "mellum2").model_config(config, True)
    assert built["layer_types"] == PERIOD * (config["num_hidden_layers"] // 4)
    assert (built["n_routed_experts"], built["experts_held"],
            built["num_experts_per_tok"], built["n_shared_experts"],
            built["first_k_dense_replace"]) == (64, 8, 8, 0, 0)
    assert (built["hidden_size"], built["num_attention_heads"],
            built["num_key_value_heads"], built["head_dim"],
            built["moe_intermediate_size"], built["sliding_window"]) == (
        2304, 32, 4, 128, 896, 1024)
    assert built["rope_parameters"] == MELLUM2_PUBLISHED["rope_parameters"]
    assert built["scoring_func"] == "softmax" and built["attention"] == "gqa"


def test_mellum2_flops_reproduce_the_issue_counts():
    """ISSUE 36's table: attention 21,233,664; router 147,456; 8 experts of
    6,193,152; two norm scales 4,608; a layer 70,930,944; 8 layers
    567,447,552; embedding and head 56,623,104; 624,072,960 with the final
    norm.  Forward operations a token at T = 8192 on an even spread: 8 x
    55.2M of projections, router and held experts, 56.6M of head, 228.6M
    of scores (a full layer 67.1M, a sliding one 15.7M)."""
    cell = mf.load_cell(MELLUM2_CELL)
    config, traffic = cell["config"], cell["traffic"]
    flops = mf.load_by_name("flops", "mellum2")
    parts = flops.parameter_count(config)
    assert parts["attention_a_layer"] == 2 * 2304 * 4096 + 2 * 2304 * 512 \
        == 21233664
    assert parts["router_a_layer"] == 147456
    assert parts["experts_a_layer"] == 8 * 6193152 == 49545216
    assert parts["a_layer"] == 70930944
    assert parts["embedding_and_head"] == 56623104
    layers = config["num_hidden_layers"]
    assert parts["total"] == layers * 70930944 + 56623104 + 2304 \
        == config["deployment"]["parameters_held"]
    assert parts["total"] == 624072960 or layers != 8
    assert flops.expected_rows_per_token(config) == 1.0
    assert flops.layers_of(config) == (3 * layers // 4, layers // 4)
    in_band, causal = flops.pairs_a_head(config, 8192)
    assert in_band == 1024 * 1025 // 2 + 7168 * 1024 == 7864832
    assert causal == 8192 * 8193 // 2
    fwd = flops.forward_flops_per_token(config, 8192)
    assert (fwd["projections"] + fwd["router"] + fwd["routed_experts"]) \
        / layers == pytest.approx(55.2e6, rel=2e-3)
    assert fwd["head"] == pytest.approx(56.6e6, rel=1e-3)
    sliding, full = flops.layers_of(config)
    assert fwd["scores"] == pytest.approx(
        full * 67.1e6 + sliding * 15.7e6, rel=2e-3)
    assert fwd["scores"] == pytest.approx(228.6e6, rel=1e-3) or layers != 8
    # never top_k rows a token: that is eight chips' work
    assert fwd["routed_experts"] * 8 == pytest.approx(
        flops.forward_flops_per_token(config, 8192, 8.0)["routed_experts"])
    with pytest.raises(RuntimeError):          # no counters: no guess
        flops.rows_per_token(config, {})
    per_example = flops.train_flops_per_example(config, traffic, 1.0)
    assert per_example == 3 * 8192 * fwd["total"]
    assert 2 * per_example == pytest.approx(35.7e12, rel=2e-3) or layers != 8
    # the kernels' own counts, by hand: 12 d a pair and head; q, o, dO, dQ
    # at 32 heads and k, v, dK, dV at 4, each once
    assert flops.flash_flops_per_step(config, traffic) == \
        12 * 128 * 32 * 2 * (sliding * in_band + full * causal)
    assert flops.flash_bytes_per_step(config, traffic) == \
        layers * 2 * 8192 * 4 * (32 + 4) * 128 * 2
    assert flops.experts_flops_per_step(config, 1000) == \
        1000 * 9 * 2 * 2304 * 896
    assert flops.experts_bytes_per_step(config, 10, 3) == \
        9 * (10 * (2304 + 896) + 3 * 2304 * 896) * 2


# -- the loop and the plain reference at a small size -------------------------

MELLUM_TINY = {
    "builder": "mellum2", "flops": "mellum2", "vocab_size": 256,
    "hidden_size": 64, "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 24,
    "layer_types": PERIOD * 2,
    "rope_parameters": {
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 64, "beta_fast": 4,
            "beta_slow": 1, "attention_factor": 1.2772588722239782}},
    "rms_norm_eps": 1e-6, "moe_intermediate_size": 32, "num_experts": 2,
    "num_experts_per_tok": 2, "norm_topk_prob": True,
    "initializer_range": 0.02, "router_bias_std": 0.01,
    "router_bias_from_batch": True,
    "deployment": {"num_experts_routed_over": 8, "first_expert": 2},
    "training": {"learning_rate": 1e-3},
    "reference": {"module": "mellum2", "examples": 2, "tolerance": {
        "logits": {"rel_l2": 0.03}, "loss": {"abs": 0.05},
        "route_gates": {"rel_l2": 1e-4}, "attn_sliding": {"rel_l2": 0.03},
        "attn_full": {"rel_l2": 0.03}}},
    "loss_band_first_step": [5.3, 5.8]}
MELLUM_TINY_TRAFFIC = {"driver": "train_loop", "seq_len": 64, "batch": 2,
                       "ring": 2, "offsets": 3, "warmup_steps": 1,
                       "trace_steps": 4, "zipf_exponent": 1.1}


def test_mellum2_drive_and_verify_at_a_small_size():
    """``drive`` trains the decoder (bf16 AMP, recompute) and ``verify``
    holds its test-mode forward to ``reference/mellum2.py`` on the weights
    the window left, in all five of the cell's comparisons; the counters
    say what the flops count by."""
    import jax

    import paddle_tpu as fluid

    driver = mf.load_by_name("traffic", "train_loop")
    cell = {"config": MELLUM_TINY, "traffic": MELLUM_TINY_TRAFFIC,
            "workload": {"name": "tiny", "chips": 1, "require_kernels": [],
                         "require_collectives": False,
                         "program": {"recompute": True}}}
    flops = mf.load_by_name("flops", "mellum2")
    with fluid.scope_guard(fluid.Scope()):
        state = driver.drive(cell, 3600000123 % (2 ** 31 - 1), 0.5)
        assert driver.verify(state, cell, jax.devices()) == []
        counted = flops.counted_rows(MELLUM_TINY)
    assert not state["failed"] and state["compiles_in_window"] == 0
    found = state["report"]["reference"]
    assert sorted(found) == ["attn_full_rel_l2", "attn_sliding_rel_l2",
                             "logits_rel_l2", "loss_abs",
                             "route_gates_rel_l2"]
    assert 0 < found["logits_rel_l2"] < 0.03 and found["loss_abs"] < 0.05
    assert 0 < found["attn_sliding_rel_l2"] < 0.03
    assert 0 < found["attn_full_rel_l2"] < 0.03
    assert found["route_gates_rel_l2"] < 1e-4
    assert set(state["compared"]) == {
        "loss_first", "loss_last_tenth", *found}
    assert sorted(counted) == ["0", "1", "2", "3"]
    # the window's own steps (the process's totals may hold other tests')
    inside = flops.counted_between(state["counters"]["window"], counted)
    assert {c["steps"] for c in inside.values()} == {state["steps"] + 2}
    assert 0 < flops.rows_per_token(MELLUM_TINY, inside) <= 2
    pools = state["pools"]
    assert (pools[0]["labels"][:, :-1] == pools[0]["input_ids"][:, 1:]).all()
    assert pools[0]["input_ids"].max() < 256


# -- the new readers on a recorded trace ----------------------------------------

def _recorded():
    """``mellum2_trimmed.json`` (PR 36, the builder's traced run on the
    chip): the first executions of the step module with a few ops of each
    name and tag a step; ops carry their tag as ``xplane.read`` worked it
    out, ``kernels`` the step's ``tpu_custom_call`` names,
    ``flash_blocks`` what the program's counter read."""
    with open(os.path.join(BENCH, "testdata", "mellum2_trimmed.json")) as f:
        return json.load(f)


def _ctx(recorded):
    cell = mf.load_cell(MELLUM2_CELL)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    return {"cell": cell, "peaks": peaks, "state": {},
            "trace": xplane.reduce_trace(recorded, set(recorded["kernels"])),
            "flops": mf.load_by_name("flops", cell["config"]["flops"])}


@pytest.fixture
def counter(monkeypatch):
    """The program's ``flash_blocks_total`` as the recorded run left it."""
    from paddle_tpu.observability import metrics

    def fill(blocks):
        metrics.registry().reset()
        for (kernel, kind, window), n in blocks.items():
            labels = {"window": window} if window else {}
            metrics.counter("flash_blocks_total", "test", kernel=kernel,
                            kind=kind, **labels).inc(n)

    yield fill
    metrics.registry().reset()


def test_mellum2_readers_read_the_recorded_trace(counter):
    recorded = _recorded()
    counter({tuple(key.split("/")): n
             for key, n in recorded["flash_blocks"].items()})
    ctx = _ctx(recorded)
    assert ctx["trace"]["steps"] >= 2
    values = {name: mf.load_by_name("layer_metrics", name).read(ctx)
              for name in MELLUM2_METRICS}
    assert all(isinstance(v, float) for v in values.values()), values
    kernel_s, tag_s = ctx["trace"]["kernel_s"], ctx["trace"]["tag_s"]
    flash = sum(s for k, s in kernel_s.items() if "flash_attention_" in k)
    config, traffic = ctx["cell"]["config"], ctx["cell"]["traffic"]
    sliding, full = ctx["flops"].layers_of(config)
    # operations bound it (11.2 T a step against 4.8 GB): the file keeps a
    # few ops of each kind a step, so the share reads high here; the
    # arithmetic is what is held
    assert values["swa_flash_roofline"] == pytest.approx(
        100 * 12 * 128 * 32 * 2 * (sliding * 7864832 + full * 33558528)
        / 197e12 / flash)
    assert values["swa_attention_ms_per_step"] == pytest.approx(
        1e3 * tag_s["swa_attention"])
    assert values["gqa_attention_ms_per_step"] == pytest.approx(
        1e3 * tag_s["gqa_attention"])
    # the band at 512 x 512 blocks: 45 of 256 a head, in every kernel
    assert values["flash_window_blocks_visited_share"] == pytest.approx(
        100 * 45 / 256)
    # the three kernels are there under their jvp names, both tags too
    assert {k.split("flash_attention_")[1].strip("_") for k in kernel_s
            if "flash_attention_" in k} == {"fwd", "dkv", "dq"}


def test_mellum2_readers_return_nothing_where_there_is_nothing_to_read(
        counter):
    """A program with no such kernels, tags or counter label (the parent
    of the PR that brought them): None, and no exception."""
    counter({("fwd", "visited", None): 5})      # the parent's: no label
    ctx = _ctx(_recorded())
    ctx["trace"] = dict(ctx["trace"], kernel_s={}, tag_s={"mul": 1e-3})
    for name in MELLUM2_METRICS:
        assert mf.load_by_name("layer_metrics", name).read(ctx) is None
    bert = mf.load_by_name("flops", "bert")
    ctx["trace"] = dict(ctx["trace"], kernel_s={"flash_attention_fwd": 1.0})
    assert mf.load_by_name("layer_metrics", "swa_flash_roofline").read(
        dict(ctx, flops=bert)) is not None      # BERT's flops count flash
    assert mf.load_by_name("layer_metrics", "swa_flash_roofline").read(
        dict(ctx, flops=mf.load_by_name("flops", "resnet"))) is None
