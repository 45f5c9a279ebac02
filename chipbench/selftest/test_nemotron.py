"""The ``nemotron_3_nano_30b_a3b`` configuration and its cell, checked on the
CPU: ``python -m pytest chipbench/selftest``.  Nothing here measures
anything.

The manifest's new entries (found by name) and the configuration file
against the catalog row's config, ``parameters_held`` against the program
the builder makes, ``flops/nemotron.py`` against numbers worked by hand,
the loop and the comparison with the plain reference at a small size, and
the three new readers on a trimmed recording of a traced run on the
chip."""

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

NEMOTRON_CELL = "nemotron_3_nano_30b_a3b_train_seq8192_bs2"
NEMOTRON_METRICS = ("grouped_ssd_scan_roofline", "relu2_experts_roofline",
                    "relu2_expert_layer_ms_per_step")

# https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
# config.json, as the catalog beside the model-configs guide holds it
NEMOTRON_PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
    "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072}


def _named(manifest, group, name):
    (entry,) = [e for e in manifest[group] if e["name"] == name]
    return entry


def test_nemotron_entries_validate_and_the_cell_reports_every_metric():
    manifest = mf.validate(mf.load_manifest())
    cell = mf.load_cell(NEMOTRON_CELL, manifest)
    assert cell["workload"]["chips"] == 1
    assert cell["workload"]["config"] == "nemotron_3_nano_30b_a3b"
    assert cell["workload"]["require_kernels"] == [
        "ssd_scan_fwd", "ssd_scan_bwd", "flash_attention_fwd",
        "flash_attention_dkv", "flash_attention_dq", "ragged-dot"]
    assert cell["traffic"] == dict(
        cell["traffic"], driver="train_loop", seq_len=8192, batch=2, ring=8,
        offsets=32, warmup_steps=4, trace_steps=8, zipf_exponent=1.1)
    reported = mf.metrics_of(manifest, "per_layer", NEMOTRON_CELL)
    unlisted = [m["name"] for m in manifest["per_layer"]
                if "workloads" not in m]
    assert set(unlisted) | set(NEMOTRON_METRICS) == set(reported)
    # appended together, in this order, each for this cell alone
    names = [m["name"] for m in manifest["per_layer"]]
    first = names.index(NEMOTRON_METRICS[0])
    assert names[first:first + 3] == list(NEMOTRON_METRICS)
    assert first > names.index("setup_backend_compile_s")
    for name, layer in zip(NEMOTRON_METRICS,
                           ("kernels", "kernels", "expert layer")):
        m = _named(manifest, "per_layer", name)
        assert m["workloads"] == [NEMOTRON_CELL]
        assert m["moves"] == "train_examples_per_s"
        assert m["layer"] == layer
        assert callable(mf.load_by_name("layer_metrics", name).read)
    cells = [w["name"] for w in manifest["workloads"]]
    configs = [c["name"] for c in manifest["configs"]]
    assert cells.index(NEMOTRON_CELL) > cells.index(
        "granite_4_0_h_micro_train_seq8192_bs1")
    assert configs.index("nemotron_3_nano_30b_a3b") > configs.index(
        "granite_4_0_h_micro")
    assert set(mf.metrics_of(manifest, "end_to_end", NEMOTRON_CELL)) == {
        "train_examples_per_s", "step_ms_p95", "setup_s"}


def test_nemotron_configuration_is_the_catalog_row_but_for_what_it_lists():
    config = mf.load_cell(NEMOTRON_CELL)["config"]
    entry = _named(mf.load_manifest(), "configs", "nemotron_3_nano_30b_a3b")
    assert config["source"] == entry["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
        "blob/main/config.json")
    assert sorted(config["reduced"]) == sorted(entry["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    differs = {k for k, v in NEMOTRON_PUBLISHED.items() if config[k] != v}
    assert differs == set(config["reduced"])
    assert config["published"] == {k: NEMOTRON_PUBLISHED[k]
                                   for k in differs}
    # the guide's floors: a whole period and four expert-or-later layers,
    # 8 routed experts, an eighth of the vocabulary
    pattern = NEMOTRON_PUBLISHED["hybrid_override_pattern"]
    assert pattern[:35] == pattern[:7] * 5 == "MEMEM*E" * 5
    assert config["num_hidden_layers"] == 7
    assert config["n_routed_experts"] == 8
    assert config["vocab_size"] * 8 == NEMOTRON_PUBLISHED["vocab_size"]
    share = config["deployment"]
    assert share["chips_sharing_a_layer"] * config["n_routed_experts"] \
        == share["n_routed_experts_routed_over"] == 128
    # what the builder hands models/decoder.py: the published widths
    built = mf.load_by_name("builders", "nemotron").model_config(config,
                                                                  True)
    assert built["hybrid_override_pattern"] == "MEMEM*E"
    assert (built["hidden_size"], built["num_attention_heads"],
            built["num_key_value_heads"], built["head_dim"],
            built["moe_intermediate_size"],
            built["moe_shared_expert_intermediate_size"],
            built["num_experts_per_tok"], built["n_routed_experts"],
            built["experts_held"], built["routed_scaling_factor"]) == (
        2688, 32, 2, 128, 1856, 3712, 6, 128, 8, 2.5)
    assert (built["mamba_n_heads"], built["mamba_d_head"],
            built["mamba_d_state"], built["mamba_n_groups"],
            built["mamba_d_conv"], built["mamba_chunk_size"],
            built["rms_norm_eps"]) == (64, 64, 128, 8, 4, 128, 1e-5)
    assert (built["mlp_hidden_act"], built["position_embedding_type"],
            built["tie_word_embeddings"]) == ("relu2", "nope", False)


def test_nemotron_parameters_held_are_the_built_programs():
    """The builder's training program at the published widths (built, not
    run): its parameters add up to ``parameters_held``, which
    ``flops/nemotron.py`` gives by part."""
    import math

    import paddle_tpu as fluid

    cell = mf.load_cell(NEMOTRON_CELL)
    config = cell["config"]
    with fluid.unique_name.guard():
        main = mf.load_by_name("builders", "nemotron").build(
            config, cell["workload"]["program"], dict(cell["traffic"],
                                                      seq_len=128), 1)[0]
    held = sum(math.prod(p.shape) for p in main.all_parameters())
    assert held == config["deployment"]["parameters_held"] == \
        mf.load_by_name("flops", "nemotron").parameter_count(config)[
            "total"] == 528093120


def test_nemotron_flops_reproduce_the_counts_worked_by_hand():
    """The parameters held, worked by hand: M 38.7M (in_proj 2688 x 10304 = 27.7M,
    out_proj 11.0M, the conv), E 100.1M (8 experts of 2 x 2688 x 1856,
    the shared 2 x 2688 x 3712, the router), * 23.4M, the vocabulary
    88.1M: 528M; forward a token, the mixers 3 x 40M MACs, the expert
    layers 3 x 24M, attention 57M with its scores, the head 44M."""
    cell = mf.load_cell(NEMOTRON_CELL)
    config, traffic = cell["config"], cell["traffic"]
    flops = mf.load_by_name("flops", "nemotron")
    parts = flops.parameter_count(config)
    assert parts["in_proj"] == 2688 * 10304 == 27697152
    assert parts["conv"] == 6144 * 4 + 6144
    assert parts["mamba_layer"] == 38744896
    assert parts["routed_experts_a_layer"] == 8 * 2 * 2688 * 1856
    assert parts["shared_expert_a_layer"] == 2 * 2688 * 3712
    assert parts["expert_layer"] == 100125440
    assert parts["attention_layer"] == 23399040
    assert parts["embedding_and_head"] == 2 * 16384 * 2688
    assert flops.layers_of(config) == (3, 3, 1)
    assert flops.expected_rows_per_token(config) == 6 * 8 / 128
    fwd = flops.forward_flops_per_token(config, 8192, 0.375)
    macs = {k: v / 2e6 for k, v in fwd.items()}
    assert (macs["mixer_projections"] + macs["scan"]) / 3 == \
        pytest.approx(40.1, abs=0.1)
    assert (macs["router"] + macs["routed_experts"]
            + macs["shared_expert"]) / 3 == pytest.approx(24.0, abs=0.1)
    assert macs["attention_projections"] + macs["scores"] == \
        pytest.approx(57.0, abs=0.1)
    assert macs["head"] == pytest.approx(44.0, abs=0.1)
    assert flops.train_flops_per_example(config, traffic, 0.375) == \
        3 * 8192 * fwd["total"]


def test_nemotron_scan_counts_by_hand():
    """At 4 heads of 4 in 2 groups, a state of 3, chunks of 4 and 8
    positions in two Mamba layers: a position's pairs are (4 + 1) / 2 =
    2.5; C B^T 2.5 x 2 x 3 = 15 a group, 30 for both, and a head 2.5 x 2
    x 4 = 20 within, 2 x 3 x 4 = 24 into the state and 24 out of it: 30 +
    4 x 68 = 302 a position and layer forward, three times that with the
    backward.  Bytes: x and y 2 x 16 at 2, B and C 2 x 2 x 3 at 2, Δ 4 at
    4: 104 a position and pass.  The experts: 2 products a row forward, 6
    in all, of 2 x 8 x 5 operations each."""
    flops = mf.load_by_name("flops", "nemotron")
    config = {"num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 4, "hidden_size": 8, "moe_intermediate_size": 5,
              "moe_shared_expert_intermediate_size": 6,
              "mamba_num_heads": 4, "mamba_head_dim": 4, "n_groups": 2,
              "ssm_state_size": 3, "conv_kernel": 4, "chunk_size": 4,
              "num_hidden_layers": 3, "hybrid_override_pattern": "MEM"}
    traffic = {"seq_len": 8, "batch": 1}
    assert flops.ssd_flops_per_token(config) == 302
    assert flops.ssd_flops_per_step(config, traffic) == 3 * 8 * 2 * 302
    assert flops.ssd_bytes_per_step(config, traffic) == 3 * 8 * 2 * 104
    assert flops.experts_flops_per_step(config, 10) == 10 * 6 * 2 * 8 * 5
    assert flops.experts_bytes_per_step(config, 10, 2) == \
        6 * (10 * 13 + 2 * 40) * 2


# -- the loop and the plain reference at a small size -------------------------

NEMOTRON_TINY = {
    "builder": "nemotron", "flops": "nemotron", "vocab_size": 256,
    "hidden_size": 64, "num_hidden_layers": 5,
    "hybrid_override_pattern": "MEM*E", "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "mamba_num_heads": 4,
    "mamba_head_dim": 32, "ssm_state_size": 16, "n_groups": 2,
    "conv_kernel": 4, "chunk_size": 16, "use_conv_bias": True,
    "mamba_proj_bias": False, "mlp_hidden_act": "relu2",
    "moe_intermediate_size": 32, "moe_shared_expert_intermediate_size": 48,
    "n_routed_experts": 2, "n_shared_experts": 1, "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "layer_norm_epsilon": 1e-5, "tie_word_embeddings": False,
    "initializer_range": 0.02, "router_bias_std": 0.01,
    "router_bias_from_batch": True,
    "deployment": {"n_routed_experts_routed_over": 8, "first_expert": 0},
    "training": {"learning_rate": 1e-3},
    "reference": {"module": "nemotron", "examples": 2, "tolerance": {
        "logits": {"rel_l2": 0.03}, "loss": {"abs": 0.05},
        "route_gates": {"rel_l2": 0.03}, "mamba_first": {"rel_l2": 0.03}}},
    "loss_band_first_step": [5.3, 5.9]}
NEMOTRON_TINY_TRAFFIC = {"driver": "train_loop", "seq_len": 64, "batch": 2,
                         "ring": 2, "offsets": 3, "warmup_steps": 1,
                         "trace_steps": 4, "zipf_exponent": 1.1}


def test_nemotron_drive_and_verify_at_a_small_size():
    """``drive`` trains the decoder (bf16 AMP, recompute) and ``verify``
    holds its test-mode forward to ``reference/nemotron.py`` on the
    weights the window left, in all four of the cell's comparisons."""
    import jax

    import paddle_tpu as fluid

    driver = mf.load_by_name("traffic", "train_loop")
    cell = {"config": NEMOTRON_TINY, "traffic": NEMOTRON_TINY_TRAFFIC,
            "workload": {"name": "tiny", "chips": 1, "require_kernels": [],
                         "require_collectives": False,
                         "program": {"recompute": True}}}
    with fluid.scope_guard(fluid.Scope()):
        state = driver.drive(cell, 4200000123 % (2 ** 31 - 1), 0.5)
        assert driver.verify(state, cell, jax.devices()) == []
    assert not state["failed"] and state["compiles_in_window"] == 0
    found = state["report"]["reference"]
    assert sorted(found) == ["logits_rel_l2", "loss_abs",
                             "mamba_first_rel_l2", "route_gates_rel_l2"]
    assert 0 < found["logits_rel_l2"] < 0.03 and found["loss_abs"] < 0.05
    assert 0 < found["mamba_first_rel_l2"] < 0.03
    assert found["route_gates_rel_l2"] < 0.03
    assert set(state["compared"]) == {
        "loss_first", "loss_last_tenth", *found}


# -- the new readers on a recorded trace ----------------------------------------

def _recorded():
    """``nemotron_trimmed.json`` (a traced run of the cell on one TPU v5
    lite, seed 4200000101): the first four executions of the step module
    with every kernel event and the first two ops of every Program-op tag
    of each; ops carry their tag as ``xplane.read`` worked it out,
    ``kernels`` the step's ``tpu_custom_call`` names, ``counted`` what the
    program's expert counters read where the windows opened and closed
    (``state["counters"]``)."""
    with open(os.path.join(BENCH, "testdata", "nemotron_trimmed.json")) as f:
        return json.load(f)


def _ctx(recorded):
    cell = mf.load_cell(NEMOTRON_CELL)
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    return {"cell": cell, "peaks": peaks,
            "state": {"counters": recorded["counted"]},
            "trace": xplane.reduce_trace(recorded, set(recorded["kernels"])),
            "flops": mf.load_by_name("flops", cell["config"]["flops"])}


def test_nemotron_readers_read_the_recorded_trace():
    recorded = _recorded()
    ctx = _ctx(recorded)
    assert ctx["trace"]["steps"] >= 2
    values = {name: mf.load_by_name("layer_metrics", name).read(ctx)
              for name in NEMOTRON_METRICS}
    assert all(isinstance(v, float) for v in values.values()), values
    kernel_s, tag_s = ctx["trace"]["kernel_s"], ctx["trace"]["tag_s"]
    config, traffic = ctx["cell"]["config"], ctx["cell"]["traffic"]
    flops = ctx["flops"]
    # the scan: bytes bound it, 3 passes x 16384 positions x 3 layers x
    # ((2 x 4096 + 2 x 8 x 128) x 2 + 64 x 4) bytes against 3 x 16384 x 3
    # x (8 x 64.5 x 256 + 64 x (64.5 x 128 + 65536)) operations
    scan = sum(s for k, s in kernel_s.items() if "ssd_scan_" in k)
    least = 3 * 16384 * 3 * ((2 * 4096 + 2 * 8 * 128) * 2 + 64 * 4) / 819e9
    assert flops.ssd_bytes_per_step(config, traffic) / 819e9 == \
        pytest.approx(least)
    assert flops.ssd_flops_per_step(config, traffic) / 197e12 < least
    assert values["grouped_ssd_scan_roofline"] == pytest.approx(
        100 * least / scan)
    # the experts: the rows the counters show in the traced window
    layers = flops.counted_between(recorded["counted"]["trace"],
                                   recorded["counted"]["end"])
    assert sorted(layers) == ["1", "3", "6"]
    rows = sum(sum(c["rows"]) / c["steps"] for c in layers.values())
    grouped = sum(s for k, s in kernel_s.items() if "ragged-dot" in k)
    busy = sum(1 for c in layers.values() for r in c["rows"]
               if r >= c["steps"])
    least = max(rows * 6 * 2 * 2688 * 1856 / 197e12,
                6 * (rows * (2688 + 1856) + busy * 2688 * 1856) * 2 / 819e9)
    assert values["relu2_experts_roofline"] == pytest.approx(
        100 * least / grouped)
    mine = [t for t in tag_s if t in ("moe_route", "moe_experts",
                                      "moe_shared")
            or t.endswith((".dispatch", ".products", ".combine"))]
    assert {"moe_route", "moe_shared"} <= set(mine)
    assert values["relu2_expert_layer_ms_per_step"] == pytest.approx(
        1e3 * sum(tag_s[t] for t in mine))


def test_nemotron_readers_return_nothing_where_there_is_nothing_to_read():
    """A program with no scan kernel, no grouped kernel, no expert tag and
    no counters (the parent of the PR that brought them): None, and no
    exception."""
    ctx = _ctx(_recorded())
    ctx["trace"] = dict(ctx["trace"], kernel_s={}, tag_s={"mul": 1e-3})
    ctx["state"] = {"counters": {"trace": {}, "end": {}}}
    for name in NEMOTRON_METRICS:
        assert mf.load_by_name("layer_metrics", name).read(ctx) is None
    # kernels without counters: no rows to count the experts' work by
    ctx["trace"] = dict(ctx["trace"], kernel_s={"ragged-dot-gmm": 1.0,
                                                "ssd_scan_fwd": 1.0})
    assert mf.load_by_name("layer_metrics", "relu2_experts_roofline").read(
        ctx) is None
    # a configuration without a scan: no count to hold a kernel to
    assert mf.load_by_name("layer_metrics", "grouped_ssd_scan_roofline").read(
        dict(ctx, flops=mf.load_by_name("flops", "mellum2"))) is None
