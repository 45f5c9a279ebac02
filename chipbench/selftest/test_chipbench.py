"""The yardstick checked on the CPU: ``python -m pytest chipbench/selftest``.

Nothing here measures anything.  The manifest and its data files are
validated, a cell added as files only is found by name, the loop is run at
``BERT_TINY`` width, the trace reduction is held to hand-worked values on
the trimmed recorded traces under ``testdata/``, and the FLOP and byte
functions are held to numbers worked by hand.
"""

import copy
import json
import math
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from chipbench import manifest as mf  # noqa: E402
from chipbench import xplane  # noqa: E402

TINY = {"vocab_size": 1024, "hidden_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 2, "intermediate_size": 512,
        "max_position_embeddings": 128, "type_vocab_size": 2,
        "hidden_dropout_prob": 0.1, "attention_probs_dropout_prob": 0.1,
        "training": {"learning_rate": 1e-4}}
TINY_TRAFFIC = {"driver": "train_loop", "seq_len": 32, "batch": 4, "ring": 2,
                "offsets": 3, "warmup_steps": 1, "trace_steps": 4,
                "zipf_exponent": 1.1}


# -- the manifest ------------------------------------------------------------

def test_manifest_validates():
    manifest = mf.validate(mf.load_manifest())
    assert manifest["command"] == ["python3", "chipbench/run.py"]
    four = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


@pytest.mark.parametrize("breach", [
    lambda m: m["workloads"][0].update(name="has space"),
    lambda m: m["end_to_end"][0].update(unit="tokens per second"),
    lambda m: m["per_layer"][0].update(moves="no_such_metric"),
    lambda m: m["workloads"][0].update(config="no_such_config"),
    lambda m: m["end_to_end"][0].update(bound=0.5),
    lambda m: [w.update(chips=4) for w in m["workloads"][:2]],
    lambda m: m["per_layer"].append(dict(m["per_layer"][0], name="unread")),
])
def test_manifest_breaches_are_refused(breach):
    manifest = copy.deepcopy(mf.load_manifest())
    breach(manifest)
    with pytest.raises((mf.ManifestError, FileNotFoundError)):
        mf.validate(manifest)


def test_a_cell_added_as_files_only_is_found_by_name(tmp_path):
    bench = tmp_path / "chipbench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "testdata"))
    (bench / "configs" / "bert_tiny.json").write_text(json.dumps(
        dict(TINY, name="bert_tiny", builder="bert", flops="bert")))
    (bench / "traffic" / "tiny_mlm.json").write_text(
        json.dumps(TINY_TRAFFIC))
    (bench / "workloads" / "bert_tiny_train.json").write_text(json.dumps({
        "program": {}, "require_kernels": [], "require_collectives": False}))
    (bench / "layer_metrics" / "steps_traced.py").write_text(
        "def read(ctx):\n    return ctx['trace']['steps']\n")
    manifest = copy.deepcopy(mf.load_manifest())
    manifest["configs"].append({
        "name": "bert_tiny", "source": "a test", "reduced": [],
        "file": "chipbench/configs/bert_tiny.json", "why": "a test"})
    manifest["workloads"].append({
        "name": "bert_tiny_train", "config": "bert_tiny",
        "traffic": "tiny_mlm", "chips": 1, "why": "a test"})
    manifest["per_layer"].append({
        "name": "steps_traced", "unit": "steps", "better": "higher",
        "source": "device_trace", "layer": "device",
        "moves": "train_examples_per_s", "workloads": ["bert_tiny_train"]})
    mf.validate(manifest, str(bench))
    cell = mf.load_cell("bert_tiny_train", manifest, str(bench))
    assert cell["config"]["hidden_size"] == 128
    assert cell["traffic"]["batch"] == 4
    assert cell["workload"]["why"] == "a test"
    assert "steps_traced" in mf.metrics_of(manifest, "per_layer",
                                           "bert_tiny_train")
    assert "steps_traced" not in mf.metrics_of(
        manifest, "per_layer", manifest["workloads"][0]["name"])
    reader = mf.load_by_name("layer_metrics", "steps_traced", str(bench))
    assert reader.read({"trace": {"steps": 7}}) == 7
    with pytest.raises(mf.ManifestError):
        mf.load_cell("no_such_cell", manifest, str(bench))
    # a workload file that repeats its manifest entry could drift from it
    (bench / "workloads" / "bert_tiny_train.json").write_text(json.dumps({
        "program": {}, "require_kernels": [], "require_collectives": False,
        "chips": 4}))
    with pytest.raises(mf.ManifestError, match="repeats chips"):
        mf.load_cell("bert_tiny_train", manifest, str(bench))


# -- the loop ----------------------------------------------------------------

class _FakeExecutor:
    """Stands in for ``Executor``: step k's loss is ``losses[k]``."""

    def __init__(self, losses):
        self.losses, self.calls = list(losses), 0

    def run(self, program, feed, fetch_list, return_numpy):
        assert return_numpy is False and set(feed) == {"x"}
        self.calls += 1
        return [self.losses[self.calls - 1]]


def _loop(losses):
    import numpy as np

    driver = mf.load_by_name("traffic", "train_loop")
    pools = [{"x": np.arange(6.0) + 10 * k} for k in range(2)]
    exe = _FakeExecutor(losses)
    return driver, exe, driver.Loop(exe, None, None,
                                    driver.Batches(pools, 4, 3))


def test_loop_takes_one_sample_per_step_with_one_in_flight():
    driver, exe, loop = _loop([float(k) for k in range(100)])
    out = loop.run(steps=5)
    assert out["steps"] == len(out["intervals_s"]) == 5
    # one uncounted step opens the window, one in flight is drained after it
    assert exe.calls == 5 + 2 and len(loop.losses) == 7
    assert loop.losses[out["first"]:out["first"] + out["steps"]] == [
        1.0, 2.0, 3.0, 4.0, 5.0]
    assert math.isclose(sum(out["intervals_s"]), out["window_s"])
    assert len(loop.dispatch_s) == exe.calls


def test_loop_records_a_non_finite_loss():
    driver, _, loop = _loop([7.0, 6.5, float("nan"), 6.0, 5.5, 5.0, 4.5])
    out = loop.run(steps=4)
    window = loop.losses[out["first"]:out["first"] + out["steps"]]
    problems, _, _ = driver.check_losses(loop.losses[0], window, [6.0, 8.0])
    assert "a loss is not finite" in problems
    problems, head, tail = driver.check_losses(9.0, [7.0, 6.0, 6.5],
                                               [6.0, 8.0])
    assert (head, tail) == (7.0, 6.5) and len(problems) == 1
    assert "outside the untrained band" in problems[0]
    problems, _, _ = driver.check_losses(7.0, [6.0, 6.0], [6.0, 8.0])
    assert "loss did not fall" in problems[0]


def test_every_step_feeds_a_new_batch():
    driver = mf.load_by_name("traffic", "train_loop")
    import numpy as np

    pools = [{"x": np.arange(6.0) + 10 * k} for k in range(2)]
    batches = driver.Batches(pools, 4, 3)
    seen = [tuple(batches.next()["x"]) for _ in range(6)]
    assert len(set(seen)) == 6            # ring 2 x offsets 3
    assert tuple(batches.next()["x"]) == seen[0]


def test_train_loop_on_bert_tiny():
    import numpy as np

    import paddle_tpu as fluid

    builder = mf.load_by_name("builders", "bert")
    driver = mf.load_by_name("traffic", "train_loop")
    startup, program, loss, _ = builder.build(
        TINY, {"fused_ln": True, "fused_qkv": True}, TINY_TRAFFIC, 7)
    pools = builder.make_pools(TINY, TINY_TRAFFIC, np.random.default_rng(7))
    assert len(pools) == 2 and pools[0]["input_ids"].shape == (6, 32)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        out = driver.measure(exe, program, loss, pools, TINY_TRAFFIC, 0.5)
    assert out["steps"] == len(out["intervals_s"]) >= 1
    assert out["window_s"] >= 0.5
    assert out["compiles_in_window"] == 0
    assert [w["compiled"] for w in out["warmup"]] == [[]]
    assert len(out["losses"]) == 1 + 1 + out["steps"] + 2
    assert all(math.isfinite(x) for x in out["losses"])
    assert 6.0 < out["losses"][0] < 8.0   # ln(1024) = 6.93


# -- the driver's two halves, and the plain references -----------------------

def _tiny_cell(fused_qkv):
    config = dict(TINY, builder="bert", loss_band_first_step=[6.0, 8.0],
                  reference={"module": "bert", "examples": 4, "tolerance": {
                      "logits": {"rel_l2": 0.02}, "loss": {"abs": 0.05}}})
    return {"config": config, "traffic": TINY_TRAFFIC, "workload": {
        "name": "tiny", "chips": 1, "require_kernels": [],
        "require_collectives": False,
        "program": {"fused_ln": True, "fused_qkv": fused_qkv}}}


@pytest.mark.parametrize("fused_qkv", [True, False])
def test_drive_and_verify_on_bert_tiny(fused_qkv, monkeypatch):
    """``drive`` yields the end-to-end values and ``verify`` holds the
    program's test-mode forward to the plain reference, on the weights the
    window left."""
    import jax

    import paddle_tpu as fluid

    driver = mf.load_by_name("traffic", "train_loop")
    cell = _tiny_cell(fused_qkv)
    with fluid.scope_guard(fluid.Scope()):
        state = driver.drive(cell, 7, 0.5)
        assert set(state["metrics"]) == {"train_examples_per_s",
                                         "step_ms_p95"}
        assert state["metrics"]["train_examples_per_s"] == pytest.approx(
            state["steps"] * 4 / state["window_s"])
        assert state["attempted"] == state["steps"] and not state["failed"]
        assert driver.verify(state, cell, jax.devices()) == []
        found = state["report"]["reference"]
        assert 0 < found["logits_rel_l2"] < 0.02
        assert state["op_names"] and state["kernels"] == {}
        # each number that decided it, beside its limit
        assert state["compared"]["logits_rel_l2"] == {
            "value": found["logits_rel_l2"], "max": 0.02}
        assert state["compared"]["loss_first"] == {
            "value": state["losses"][0], "min": 6.0, "max": 8.0}
        assert set(state["compared"]) == {
            "loss_first", "loss_last_tenth", "logits_rel_l2", "loss_abs"}
        # the same check refuses a forward that is not the reference's
        real = mf.load_by_name

        class Skewed:
            @staticmethod
            def forward(w, feed, config):
                out = real("reference", "bert").forward(w, feed, config)
                return {"logits": 1.05 * out["logits"],
                        "loss": out["loss"] + 0.2}

        monkeypatch.setattr(mf, "load_by_name", lambda kind, name: Skewed
                            if kind == "reference" else real(kind, name))
        problems, found = driver.compare_with_reference(
            state["exe"], state["builder"], cell, state["pools"])
    assert len(problems) == 2 and "from the plain reference" in problems[0]
    assert found["logits_rel_l2"] > 0.02 and found["loss_abs"] > 0.05


def test_reference_distances_by_hand():
    driver = mf.load_by_name("traffic", "train_loop")
    assert driver.distance("rel_l2", [3.0, 4.0], [3.0, 0.0]) == \
        pytest.approx(4.0 / 3.0)
    assert driver.distance("abs", [1.0, -2.5], [1.5, -2.0]) == 0.5
    assert driver.distance("abs", [1.0, 2.0], [1.0]) == float("inf")
    with pytest.raises(ValueError):
        driver.distance("cosine", [1.0], [1.0])


def test_resnet_reference_agrees_with_the_program():
    """The test-mode ResNet-50 against the plain reference on the startup
    program's weights, two images at the published size; the reference
    reads from the weights that ``models/resnet.py`` projects the shortcut
    in every block."""
    import numpy as np

    import jax
    import paddle_tpu as fluid

    cell = mf.load_cell("resnet50_train_bs128")
    config = dict(cell["config"], reference=dict(
        cell["config"]["reference"], examples=2))
    builder = mf.load_by_name("builders", "resnet")
    driver = mf.load_by_name("traffic", "train_loop")
    traffic = dict(cell["traffic"], batch=2, ring=1, offsets=1)
    startup, _, _, _ = builder.build(config, {}, traffic, 11)
    pools = builder.make_pools(config, traffic, np.random.default_rng(11))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        problems, found = driver.compare_with_reference(
            exe, builder, dict(cell, config=config, traffic=traffic), pools)
        projections = sum(
            1 for k in range(65) if fluid.global_scope().get(
                "conv2d_%d.w_0" % k).shape[2:] == (1, 1)) - 2 * 16
    assert problems == [] and 0 < found["logits_rel_l2"] < 0.02
    assert projections == 16      # the paper's network has 4


def test_a_moved_executor_internal_is_named(monkeypatch):
    from paddle_tpu import executor

    driver = mf.load_by_name("traffic", "train_loop")
    monkeypatch.setattr(executor, "_LAST_COMPILED_BLOCK", None)
    with pytest.raises(RuntimeError, match="_LAST_COMPILED_BLOCK"):
        driver.relower_last_step({}, 1)


# -- the device gate ---------------------------------------------------------

def test_cli_exits_non_zero_without_a_tpu():
    cell = mf.load_manifest()["workloads"][0]["name"]
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout == ""
    assert "TPU only" in done.stderr


def test_cli_exits_non_zero_where_only_the_benchmark_is(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = mf.load_manifest()["workloads"][0]["name"]
    done = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", cell, "--seed",
         "1", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        capture_output=True, text=True, timeout=300)
    assert done.returncode != 0
    assert done.stdout == ""
    assert "nothing to measure" in done.stderr


def test_unknown_device_kind_raises():
    sys.path.insert(0, BENCH)
    import run

    assert run.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        run.peaks_for("TPU v9")
    with pytest.raises(KeyError):
        run.peaks_for("cpu")


def test_peak_memory_is_the_runtime_counters_alone():
    sys.path.insert(0, BENCH)
    import run

    # memory_stats() of the chip under resnet50_train_bs128 (PERF.md, PR 24)
    assert run.peak_bytes({
        "bytes_in_use": 2945861632, "peak_bytes_in_use": 3022935040,
        "bytes_reserved": 5594039296, "peak_bytes_reserved": 5594039296,
        "bytes_limit": 16909336064}) == 3022935040 + 5594039296
    # a runtime without the second counter reports the first alone
    assert run.peak_bytes({"peak_bytes_in_use": 8 * 10 ** 9}) == 8 * 10 ** 9
    assert run.peak_bytes({}) == 0


# -- operations and bytes ----------------------------------------------------

@pytest.mark.parametrize("seq_len, scored, per_token", [
    # a layer: 8 d^2 + 4 d ff = 8 x 589,824 + 4 x 2,359,296 = 14,155,776,
    # plus scores and context 4 T d = 3,072 T.  The head scores int(0.15 T)
    # + 1 positions a sequence: 2 d V = 46,881,792 times scored / T.
    # T 128: 12 x (14,155,776 + 393,216) = 174,587,904; head x 20 / 128 =
    # 7,325,280; forward 181,913,184; training three forwards' worth
    (128, 20, 545739552),
    # T 512: 12 x (14,155,776 + 1,572,864) = 188,743,680; head x 77 / 512
    # = 7,050,582; forward 195,794,262
    (512, 77, 587382786),
])
def test_bert_flops_by_hand(seq_len, scored, per_token):
    from paddle_tpu.models import bert

    cell = mf.load_cell("bert_base_train_seq128_bs128")
    ours = mf.load_by_name("flops", "bert")
    assert ours.max_pred(seq_len) == scored == \
        bert.default_max_pred(seq_len)
    assert ours.train_flops_per_token(cell["config"], seq_len) == per_token
    assert ours.train_flops_per_example(
        cell["config"], {"seq_len": seq_len}) == seq_len * per_token


def test_resnet_flops_by_hand():
    """4.09 G multiply-accumulates a forward image at 224 x 224 (the
    torchvision / fvcore count), two operations each, three forwards'
    worth a training step: 24.54 G."""
    cell = mf.load_cell("resnet50_train_bs128")
    ours = mf.load_by_name("flops", "resnet")
    assert ours.train_flops_per_example(cell["config"], cell["traffic"]) \
        == pytest.approx(24.54e9, rel=1e-12)
    with pytest.raises(ValueError):
        ours.train_flops_per_example(dict(cell["config"], depth=101), {})


def test_kernel_bytes_and_flops_by_hand():
    ours = mf.load_by_name("flops", "bert")
    config = mf.load_cell("bert_base_train_seq128_bs128")["config"]
    traffic = {"batch": 2, "seq_len": 512}
    # 25 sites x (fwd + bwd) x (4 bf16 passes over 1024x768 + 2 f32 stats)
    assert ours.fused_ln_bytes_per_step(config, traffic) == \
        25 * 2 * (4 * 1024 * 768 * 2 + 2 * 1024 * 4)
    # 12 layers x 2 sequences x 12 heads x 6 matmuls x 2*512*512*64
    assert ours.flash_flops_per_step(config, traffic) == \
        12 * 2 * 12 * 6 * 2 * 512 * 512 * 64
    assert ours.flash_bytes_per_step(config, traffic) == \
        12 * 12 * 2 * 512 * 768 * 2


def test_peak_table_names_its_source():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    assert "TPU v5e" in table["source"]
    assert table["devices"]["TPU v5 lite"] == {
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9}


# -- the trace reduction -----------------------------------------------------

def test_interval_arithmetic():
    assert xplane.union([[5, 7], [0, 2], [1, 3], [7, 8]]) == [[0, 3], [5, 8]]
    assert xplane.length([[0, 3], [5, 8]]) == 6
    assert xplane.subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert xplane.subtract([[0, 4], [6, 9]], [[3, 7]]) == [[0, 3], [7, 9]]
    assert xplane.subtract([[0, 4]], []) == [[0, 4]]


def test_names_and_categories():
    assert xplane.program_op("jit(run)/pd12_mul/pd40_mul_grad/dot") == \
        "mul_grad"
    assert xplane.program_op("jit(run)/transpose") == ""
    assert xplane.category("%fusion.3", "mul_grad") == "matmul/conv"
    assert xplane.category("%fusion.3", "conv2d") == "matmul/conv"
    assert xplane.category("%fusion.3", "fused_conv_bn_act_grad") == \
        "matmul/conv"
    assert xplane.category("%fusion.9", "softmax") == "attention"
    assert xplane.category("%x", "fused_multihead_attention_grad") == \
        "attention"
    assert xplane.category("%x", "fused_dropout_add_ln") == "fused-ln-glue"
    assert xplane.category("%x", "adam") == "optimizer"
    assert xplane.category("%x", "layer_norm_grad") == "norm"
    assert xplane.category("%x", "elementwise_mul") == "elementwise"
    assert xplane.category("%convert.1", "") == "other"
    assert xplane.category("%all-reduce.7", "adam") == "collectives"
    assert xplane.is_async_span("%all-reduce-start.2")
    assert not xplane.is_async_span("%all-reduce-done.2")
    kernels = {"fused_ln_fwd", "jvp_fused_ln_fwd_"}
    assert xplane.kernel_of("fused_ln_fwd.12", kernels) == "fused_ln_fwd"
    assert xplane.kernel_of("jvp_fused_ln_fwd_", kernels) == \
        "jvp_fused_ln_fwd_"
    assert xplane.kernel_of("%fusion.12", kernels) is None


@pytest.mark.parametrize("op_name, tag", [
    # ``op_name`` paths of the kanana step's compiled text (the cell's
    # builder at a small size, every layer under ``recompute()``), the
    # path's last element, the primitive, shortened.  The forward pass:
    ("jit(step_once)/pd2_recompute_block/pd15_mla_attention/dot_general",
     "mla_attention"),
    # the region's re-run, and its backward
    ("jit(step_once)/pd22_recompute_block_grad/jvp(pd22_mla_attention)/"
     "bhqd,bhkd->bhqk/dot_general", "mla_attention"),
    ("jit(step_once)/pd22_recompute_block_grad/transpose(jvp("
     "pd22_mla_attention))/jit(_where)/select_n", "mla_attention"),
    ("jit(step_once)/pd23_recompute_block_grad/transpose(jvp(pd30_moe_route"
     "))/jit(take_along_axis)/scatter-add", "moe_route"),
    ("jit(step_once)/pd23_recompute_block_grad/transpose(jvp(pd42_moe_shared"
     "))/jit(silu)/add_any", "moe_shared"),
    # a part of an op, forward: two scopes, the innermost wins
    ("jit(step_once)/pd2_recompute_block/pd37_moe_experts/while/body/"
     "pd37_moe_experts.combine/scatter-add", "moe_experts.combine"),
    ("jit(step_once)/pd23_recompute_block_grad/jvp(pd37_moe_experts)/"
     "pd37_moe_experts.dispatch/jit(argsort)/sort", "moe_experts.dispatch"),
    # a part in the backward of the layer's ``custom_vjp``: the program
    # names it after the op it lowered last; the part is its op's
    ("jit(step_once)/pd23_recompute_block_grad/transpose("
     "pd23_recompute_block_grad)/jvp(pd37_moe_experts)/while/body/transpose("
     "jvp(pd46_elementwise_add.products))/transpose(jvp())/concatenate",
     "moe_experts.products"),
    ("jit(step_once)/pd23_recompute_block_grad/transpose("
     "pd23_recompute_block_grad)/jvp(pd37_moe_experts)/while/body/"
     "pd46_elementwise_add.dispatch/jit(floor_divide)/sign",
     "moe_experts.dispatch"),
    # inside the op, outside any part
    ("jit(step_once)/pd23_recompute_block_grad/transpose("
     "pd23_recompute_block_grad)/jvp(pd37_moe_experts)/while/cond/lt",
     "moe_experts"),
    # the region's own: no inner scope
    ("jit(step_once)/pd23_recompute_block_grad/optimization_barrier",
     "recompute_block_grad"),
    # outside any region, as before
    ("jit(step_once)/pd19_lm_head/transpose(jvp())/dot_general", "lm_head"),
    ("jit(step_once)/pd693_mul_grad/transpose(jvp())/dot_general",
     "mul_grad"),
    # a path with no scope of the Executor's
    ("rw['decoder.layer1.moe.experts.0.down']", ""),
    ("reduce_window_sum", ""),
    # a name that merely ends in a scope's letters is none
    ("jit(step_once)/xpd3_mul/dot_general", ""),
])
def test_the_tag_is_the_same_in_every_pass(op_name, tag):
    assert xplane.program_op(op_name) == tag


def test_an_event_that_holds_others_is_not_summed_with_them():
    """Hand-made: a ``while`` [1100, 1700) over three events of its body
    (two fusions and a kernel), a ``conditional`` over one, and a fusion
    of its own; steps of 1000 ns, two in the window.  The body counts
    once, the containers not at all; busy is the union as before."""
    dev = {"modules": [["jit_step(1)", 0, 900], ["jit_step(1)", 1000, 900],
                       ["jit_step(1)", 2000, 900]],
           "ops": [["while.3", 1100, 600, "moe_experts"],
                   ["fusion.1", 1100, 200, "moe_experts.dispatch"],
                   ["ragged-dot-none.2", 1300, 300, ""],
                   ["fusion.2", 1600, 100, "moe_experts.combine"],
                   ["conditional.1", 1750, 50, "adam"],
                   ["fusion.7", 1760, 30, "adam"],
                   ["fusion.9", 2100, 400, "mul"]]}
    out = xplane.reduce_trace({"devices": {"d": dev}, "host": []},
                              {"ragged-dot-none"})
    assert xplane.containers(dev["ops"]) == {id(dev["ops"][0]),
                                             id(dev["ops"][4])}
    assert out["steps"] == 2
    assert out["busy_s"] == pytest.approx((600 + 50 + 400) * 1e-9)
    assert out["tag_s"] == {
        "moe_experts.dispatch": pytest.approx(100e-9),
        "~ragged-dot-none": pytest.approx(150e-9),
        "moe_experts.combine": pytest.approx(50e-9),
        "adam": pytest.approx(15e-9), "mul": pytest.approx(200e-9)}
    assert out["category_s"] == {
        "other": pytest.approx(300e-9),       # the three of the loop's body
        "optimizer": pytest.approx(15e-9),
        "matmul/conv": pytest.approx(200e-9)}
    assert out["kernel_s"] == {"ragged-dot-none": pytest.approx(150e-9)}
    assert sum(out["category_s"].values()) <= out["busy_s"] / out["steps"]
    # a loop whose body the trace does not show is an event like another
    assert xplane.containers([["while.1", 0, 10, ""], ["b", 10, 5, ""],
                              ["call.2", 14, 5, ""]]) == set()
    # on the chip a copy holds an empty custom call, a fusion a
    # slice-done: events that overlap, not bodies
    assert xplane.containers([["copy.3014", 0, 172, ""],
                              ["custom-call.1020", 0, 0, ""],
                              ["fusion.7", 200, 50, "adam"],
                              ["slice-done.2", 210, 5, ""]]) == set()


def test_hlo_names_and_tags():
    text = (
        '  %fusion.3348 = (bf16[3072]{0}, bf16[16384,3072]{1,0}) fusion('
        'bf16[16384,3072]{1,0} %p), kind=kOutput, calls=%fused_computation.9'
        ', metadata={op_name="jit(step_once)/pd693_mul_grad/transpose(jvp())'
        '/dot_general" source_file="math.py" source_line=32}\n'
        '  ROOT %copy.7 = f32[768]{0} copy(f32[768]{0} %x)\n'
        '  %fused_ln_fwd.25 = (bf16[16384,768]{1,0}) custom-call(bf16[16384,'
        '768]{1,0} %a), custom_call_target="tpu_custom_call", metadata={'
        'op_name="jit(step_once)/pd88_fused_dropout_add_ln/pallas_call"}\n')
    names = xplane.op_names_in(text)
    assert names == {
        "fusion.3348": "jit(step_once)/pd693_mul_grad/transpose(jvp())"
                       "/dot_general",
        "fused_ln_fwd.25": "jit(step_once)/pd88_fused_dropout_add_ln"
                           "/pallas_call"}
    assert xplane.short_name(
        "%fusion.3348 = (bf16[3072]{0}) fusion(bf16[8]{0} %p)") == \
        "fusion.3348"
    assert xplane.program_op(names["fused_ln_fwd.25"]) == \
        "fused_dropout_add_ln"


def _trimmed():
    with open(os.path.join(BENCH, "testdata",
                           "bert_seq128_trimmed.json")) as f:
        return json.load(f)


def test_reduction_of_the_recorded_trace():
    """Against values worked by hand from the file's 72 ops: the window
    runs from the second execution's start (100,001,605 ns) to the fourth's
    end (400,001,983 ns), three steps; the 54 ops inside it do not overlap
    except a 1-2 ns copy-start just before each copy-done."""
    trace = _trimmed()
    kernels = {"fused_ln_fwd", "jvp_fused_ln_fwd_", "jvp_fused_ln_bwd_"}
    out = xplane.reduce_trace(trace, kernels)
    assert out["steps"] == 3
    assert out["window_s"] == pytest.approx(300000378e-9, rel=1e-12)
    assert out["busy_s"] == pytest.approx(10683049e-9, rel=1e-12)
    assert out["idle_share"] == pytest.approx(1 - 10683049 / 300000378)
    assert out["kernel_calls"] == {k: pytest.approx(2.0) for k in kernels}
    assert out["kernel_s"] == {
        "fused_ln_fwd": pytest.approx(555598e-9 / 3),
        "jvp_fused_ln_fwd_": pytest.approx(521748e-9 / 3),
        "jvp_fused_ln_bwd_": pytest.approx(684697e-9 / 3)}
    assert out["category_s"] == {
        "matmul/conv": pytest.approx(8404773e-9 / 3),     # 9 'mul' fusions
        "fused-ln-glue": pytest.approx(1762043e-9 / 3),   # the 18 kernel calls
        "other": pytest.approx(427686e-9 / 3),            # 3 copy-done
        "attention": pytest.approx(87093e-9 / 3),         # 3 softmax fusions
        "elementwise": pytest.approx(1201e-9 / 3),        # 3 dropout reshapes
        "optimizer": pytest.approx(193e-9 / 3)}           # 6 adam fusions
    assert out["collective_s"] == out["collective_exposed_s"] == 0.0
    # what the loop was doing in the idle gaps (almost all of this trimmed
    # window is gap): blocked on the loss, or inside Executor.run
    assert out["gaps"] == [("wait_loss", pytest.approx(0.270066591)),
                           ("dispatch", pytest.approx(0.019250738))]


def test_reduction_agrees_with_a_brute_force_count():
    trace = _trimmed()
    dev = trace["devices"]["/device:TPU:0"]
    lo, hi, steps = xplane.steady_window(dev)
    ops = [o for o in dev["ops"] if o[1] >= lo and o[1] + o[2] <= hi]
    points = sorted({lo, hi} | {o[1] for o in ops}
                    | {o[1] + o[2] for o in ops})
    busy = sum(b - a for a, b in zip(points, points[1:])
               if any(o[1] <= a and o[1] + o[2] >= b for o in ops))
    out = xplane.reduce_trace(trace, ())
    assert steps == 3 and out["busy_s"] == pytest.approx(busy / 1e9)
    assert out["kernel_s"] == {}


def test_steady_window_wants_three_executions():
    dev = {"modules": [["jit_step(1)", 0, 10], ["jit_step(1)", 20, 10]],
           "ops": [["fusion.1", 1, 5, "mul"]]}
    assert xplane.steady_window(dev) is None
    assert xplane.reduce_trace({"devices": {"d": dev}, "host": []}) is None


def test_exposed_collective_time_by_hand():
    """Hand-made: a synchronous all-reduce [1100,1200) that a fusion
    overlaps from 1150, and an async pair whose start event [1300,1500)
    lasts while a fusion runs [1320,1400)."""
    dev = {"modules": [["jit_step(1)", 0, 900], ["jit_step(1)", 1000, 900],
                       ["jit_step(1)", 2000, 900]],
           "ops": [["all-reduce.1", 1100, 100, "adam"],
                   ["fusion.2", 1150, 110, "mul"],
                   ["all-reduce-start.3", 1300, 200, ""],
                   ["fusion.4", 1320, 80, "mul_grad"],
                   ["all-reduce-done.3", 1500, 10, ""],
                   ["fusion.5", 2100, 700, "mul"]]}
    out = xplane.reduce_trace({"devices": {"/device:TPU:0": dev,
                                           "/device:TPU:1": dev},
                               "host": []})
    assert out["steps"] == 2 and out["window_s"] == pytest.approx(1900e-9)
    # collectives cover [1100,1200) + [1300,1510) = 310 ns over 2 steps
    assert out["collective_s"] == pytest.approx(310e-9 / 2)
    # not covered by another op: [1100,1150) + [1300,1320) + [1400,1510)
    assert out["collective_exposed_s"] == pytest.approx(180e-9 / 2)
    # busy: [1100,1260) + [1300,1510) + [2100,2800) = 1070 ns
    assert out["busy_s"] == pytest.approx(1070e-9)
    assert out["category_s"]["collectives"] == pytest.approx(110e-9 / 2)
    assert out["category_s"]["matmul/conv"] == pytest.approx(890e-9 / 2)
