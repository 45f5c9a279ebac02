"""``chip_smoke.py`` off the chip: it refuses to run, its phase functions
work end to end at ``BERT_TINY`` width when a test hands them the config,
and the helpers it leans on (the compile-cache rule, the one Pallas gating
predicate) answer as documented."""

import copy
import importlib.util
import json
import os
import sys
import warnings

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

import paddle_tpu.ops.pallas as pallas
from paddle_tpu.core import configure_compile_cache
from paddle_tpu.models import bert

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def chip_smoke():
    return _load("chip_smoke.py", "chip_smoke")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(REPO,
                                                                     path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _phase_lines(capsys):
    return [json.loads(l) for l in capsys.readouterr().out.splitlines()
            if l.startswith("{")]


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_to_run_without_a_tpu(chip_smoke, capsys, argv):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main(argv)
    assert exc.value.code not in (0, None)
    assert "no TPU" in str(exc.value.code)
    out = capsys.readouterr().out
    assert '"ok"' not in out and '"phase"' not in out


def test_compile_cache_left_to_jax_when_env_names_it(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))

    def no_update(name, value):
        raise AssertionError("jax.config.update(%r) called" % name)

    monkeypatch.setattr(jax.config, "update", no_update)
    assert configure_compile_cache() == str(tmp_path)


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert configure_compile_cache() == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()


@pytest.mark.parametrize("mode,platform,partitioned,want", [
    (None, "cpu", False, (False, False)),        # the CPU backend: composites
    ("interpret", "cpu", False, (True, True)),
    (None, "tpu", False, (True, False)),         # the chip: kernels
    (None, "tpu", True, (False, False)),         # GSPMD cannot split Mosaic
    ("interpret", "cpu", True, (True, True)),    # plain HLO partitions fine
    ("off", "tpu", False, (False, False)),
])
def test_gating_predicate(monkeypatch, mode, platform, partitioned, want):
    if mode is None:
        monkeypatch.delenv("PADDLE_TPU_PALLAS", raising=False)
    else:
        monkeypatch.setenv("PADDLE_TPU_PALLAS", mode)
    if platform == "cpu":
        assert pallas.device_platform() == "cpu"  # the real answer here
    else:
        monkeypatch.setattr(pallas, "device_platform", lambda: platform)
    if not partitioned:
        assert pallas.use_pallas() == want
        return
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        with pallas.gspmd_partitioned():
            assert pallas.use_pallas() == want
    # routing kernels away on the chip is said out loud, once per step
    assert bool(caught) == (platform == "tpu")
    assert pallas.use_pallas() != (False, False)  # restored on exit


@pytest.mark.parametrize("rows,want", [
    (8192, True),   # BERT-base seq128 bs64: 256-row blocks
    (128, True),    # one served row: a single block
    (24, True),     # a single block again (bn == n)
    (264, False),   # only 8-row blocks divide it: (1, 8) statistics tiles
    (8200, False),  # likewise
    (100, False),   # not a multiple of the 8-row tile at all
])
def test_fused_ln_selects_only_rows_the_lowering_accepts(monkeypatch, rows,
                                                        want):
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import fused_ln

    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    monkeypatch.delenv("PADDLE_TPU_FUSED_LN_BLOCK_ROWS", raising=False)
    assert fused_ln._eligible(jnp.zeros((rows, 768), jnp.bfloat16)) is want


@pytest.mark.parametrize("nblocks,want", [
    (9216, True),   # a BERT-base FFN gradient in 256-element blocks
    (8, True),      # one grid step (bn == nblocks)
    (2112, False),  # 64-block row tiles: (1, 64) scale tiles are refused
    (3, False),     # not a multiple of 8
])
def test_block_quant_selects_only_counts_the_lowering_accepts(monkeypatch,
                                                             nblocks, want):
    from paddle_tpu.quant import blockwise

    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    monkeypatch.delenv("PADDLE_TPU_QUANT_BLOCK_ROWS", raising=False)
    assert blockwise._eligible(nblocks, 256) is want


@pytest.mark.parametrize("path,argv", [
    ("tools/bench_flash.py", ["bench_flash.py"]),
    ("tools/validate_flash_prng.py", ["validate_flash_prng.py"]),
    ("tools/validate_fused_ln.py", ["validate_fused_ln.py"]),
    ("tools/bench_pure_jax.py", ["bench_pure_jax.py"]),
])
def test_chip_tools_refuse_the_cpu_backend(monkeypatch, capsys, path, argv):
    monkeypatch.setattr(sys, "argv", argv)
    tool = _load(path, "tool_under_test")
    with pytest.raises(SystemExit) as exc:
        tool.main()
    assert "runs on the chip only" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_examples_backend_choice_starts_no_probe(monkeypatch):
    """``--cpu`` pins the CPU; otherwise the default backend is used. No
    subprocess probes for the device and nothing falls back."""
    import subprocess

    def no_process(*a, **kw):
        raise AssertionError("a process was started to probe the device")

    monkeypatch.setattr(subprocess, "Popen", no_process)
    common = _load("examples/_common.py", "examples_common")
    assert common.pick_backend(force_cpu=True) == "cpu"
    assert common.pick_backend() == jax.devices()[0].platform


def test_native_library_is_named_by_its_sources(tmp_path):
    """A ``.so`` built from other sources (a stale copy that travelled with
    the tree) has another name, so it can never be the one loaded."""
    from paddle_tpu import native

    src = tmp_path / "a.cc"
    src.write_text("int f() { return 1; }")
    first = native._so_path([str(src)])
    assert native._so_path([str(src)]) == first
    src.write_text("int f() { return 2; }")
    assert native._so_path([str(src)]) != first
    assert os.path.dirname(first) == os.path.dirname(native.__file__)


def test_autotune_cache_defaults_into_the_checkout(monkeypatch):
    from paddle_tpu import autotune

    monkeypatch.delenv("PADDLE_TPU_AUTOTUNE_CACHE", raising=False)
    assert autotune.cache_path().startswith(
        os.path.join(REPO, ".autotune_cache") + os.sep)


@pytest.mark.parametrize("env,platform,impl", [
    (None, "cpu", "threefry2x32"),
    (None, "tpu", "rbg"),            # ran on the chip: chip_smoke's dropout
    ("threefry", "tpu", "threefry2x32"),
])
def test_rng_key_impl_follows_the_one_platform_answer(monkeypatch, env,
                                                      platform, impl):
    from paddle_tpu.executor import rng_key

    if env is None:
        monkeypatch.delenv("PADDLE_TPU_RNG_IMPL", raising=False)
    else:
        monkeypatch.setenv("PADDLE_TPU_RNG_IMPL", env)
    monkeypatch.setattr(pallas, "device_platform", lambda: platform)
    assert impl in str(jax.random.key_impl(rng_key(7)))


def test_autotune_signatures_use_the_one_platform_answer(monkeypatch):
    from paddle_tpu.autotune import harness

    assert harness._backend() == "cpu"
    monkeypatch.setattr(pallas, "device_platform", lambda: "tpu")
    assert harness._backend() == "tpu"
    assert harness.sweep_signature("f", {"t": 1}).endswith("backend=tpu|t=1")


def test_require_tpu_names_the_caller():
    from paddle_tpu.core import require_tpu

    with pytest.raises(SystemExit) as exc:
        require_tpu("this tool")
    assert str(exc.value.code).startswith(
        "this tool runs on the chip only and JAX reports platform 'cpu'")


@pytest.mark.parametrize("asked,child_platform", [
    (None, "cpu"), ("cpu", "cpu"), ("tpu", "tpu"),
])
def test_dryrun_decides_its_devices_from_the_environment(
        monkeypatch, asked, child_platform):
    """No probe process: the virtual CPU mesh unless JAX_PLATFORMS names
    the chip, in which case the child is left to find real devices."""
    import subprocess

    entry = _load("__graft_entry__.py", "graft_entry_under_test")
    monkeypatch.delenv("PADDLE_TPU_DRYRUN_CHILD", raising=False)
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    if asked is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", asked)
    runs = []
    monkeypatch.setattr(subprocess, "run",
                        lambda cmd, env, check: runs.append(env))
    monkeypatch.setattr(entry, "_dryrun_two_process", lambda: None)
    entry.dryrun_multichip(4)
    (env,) = runs
    assert env["JAX_PLATFORMS"] == child_platform
    assert env["PADDLE_TPU_DRYRUN_CHILD"] == "1"
    forced = "--xla_force_host_platform_device_count=4" in env.get(
        "XLA_FLAGS", "")
    assert forced == (child_platform == "cpu")


def test_kernel_names_read_from_compiled_hlo():
    text = """
  %fused_ln_fwd.3 = (bf16[8,128]{1,0}) custom-call(%a), custom_call_target="tpu_custom_call", metadata={}
  %jvp_fused_ln_fwd_.7 = bf16[8,128]{1,0} custom-call(%b), custom_call_target="tpu_custom_call"
  %jvp_fused_ln_fwd_.9 = bf16[8,128]{1,0} custom-call(%c), custom_call_target="tpu_custom_call"
  %conv_bn_act_fwd = f32[8,128]{1,0} custom-call(%d), custom_call_target="tpu_custom_call"
  %other.1 = f32[8]{0} custom-call(%e), custom_call_target="Sharding"
"""
    assert pallas.pallas_kernels_in(text) == {
        "fused_ln_fwd": 1, "jvp_fused_ln_fwd_": 2, "conv_bn_act_fwd": 1}


@pytest.fixture
def tiny():
    """BERT_TINY's width, one layer deep (compile time is the test's cost)."""
    cfg = copy.copy(bert.BERT_TINY)
    cfg.layers = 1
    return cfg


@pytest.fixture
def tiny_flagship(tiny):
    tiny.fused_ln = tiny.fused_qkv = True
    return tiny


def test_phase_train_at_tiny_width(chip_smoke, capsys, tiny_flagship):
    losses = chip_smoke.phase_train("train_tiny", tiny_flagship, 32, 8, 4,
                                    ())
    (line,) = _phase_lines(capsys)
    assert line["phase"] == "train_tiny" and line["losses"] == losses
    assert len(losses) == 4 and losses[-1] < losses[0]
    assert line["fusion_families"] == {"dropout_add_ln": 1}
    assert line["kernels"] == {}  # the CPU backend runs the composites


def test_a_missing_kernel_fails_the_phase(chip_smoke):
    found = {"jvp_fused_ln_fwd_": 25, "jvp_fused_ln_bwd_": 25}
    chip_smoke._require_kernels(found, ("fused_ln_fwd", "fused_ln_bwd"), "x")
    with pytest.raises(SystemExit) as exc:
        chip_smoke._require_kernels(found, ("flash_attention_fwd",), "x")
    assert "no 'flash_attention_fwd' tpu_custom_call" in str(exc.value.code)


def test_phase_serve_at_tiny_width(chip_smoke, capsys, tiny):
    chip_smoke.phase_serve(tiny, 32, (1, 3, 2, 4, 1, 1, 2), buckets=(2, 4))
    (line,) = _phase_lines(capsys)
    assert line["phase"] == "serve" and line["buckets_used"] == [2, 4]
    assert 0 < line["rel_l2_vs_executor"] < chip_smoke.SERVE_REL_L2_TOL


def test_phase_data_parallel_at_tiny_width(chip_smoke, capsys,
                                           tiny_flagship):
    tiny = tiny_flagship
    tiny.dropout = tiny.attn_dropout = 0.0
    n = len(jax.devices())
    chip_smoke.phase_data_parallel(tiny, 32, 2 * n, 2)
    one, many, verdict = _phase_lines(capsys)
    assert one["spans"] == {"batch": 1, "gradient": 1, "parameter": 1}
    assert many["spans"] == {"batch": n, "gradient": n, "parameter": n}
    assert one["all_reduces"] == 0 and many["all_reduces"] > 0
    assert verdict["loss_rel_diff_max"] <= verdict["rtol"]


def test_phase_window_edge_at_a_small_size(chip_smoke, capsys, monkeypatch):
    """The probe of the band's edges through the flash kernels in
    interpret mode: 512 positions, 4 query heads on 2, a window of 200
    (no multiple of a block); and an edge one row off fails it, forward
    or backward."""
    import importlib

    FA = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_Q", "128")
    monkeypatch.setenv("PADDLE_TPU_FLASH_BLOCK_K", "128")
    small = dict(t=512, heads=4, kv_heads=2, d=64, window=200,
                 keys=(0, 199, 200, 311), batch=2)
    worst = chip_smoke.phase_window_edge(**small)
    (line,) = _phase_lines(capsys)
    assert line["phase"] == "window_edge" and line["keys"] == [0, 199, 200,
                                                               311]
    assert set(worst) == {"sliding", "full", "sliding_dv", "sliding_dk",
                          "full_dv", "full_dk"}
    assert max(worst.values()) < 1e-2
    real = FA._scores

    def one_row_more(x, y, sm_scale, bias, offset, keys_down, window=None):
        return real(x, y, sm_scale, bias, offset, keys_down,
                    window and window + 1)

    def forward_only(*a, **kw):     # the dK/dV kernel's orientation alone
        return (one_row_more if a[5] else real)(*a, **kw)

    for broken, said in ((one_row_more, "rows [200"), (forward_only, "off")):
        monkeypatch.setattr(FA, "_scores", broken)
        with pytest.raises(SystemExit) as exc:
            chip_smoke.phase_window_edge(**small)
        assert "window_edge" in str(exc.value.code)
        assert said in str(exc.value.code)


def test_phase_ssd_scan_at_a_small_size(chip_smoke, capsys, monkeypatch):
    """The probe of the scan kernels in interpret mode: 384 positions in
    chunks of 128, 4 heads of 64, a state of 16, bf16 operands; and a
    backward kernel whose dB is half again too large fails it."""
    import importlib

    S = importlib.import_module("paddle_tpu.ops.pallas.ssd_scan")
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    small = dict(t=384, heads=4, d_head=64, state=16, chunk=128)
    rel = chip_smoke.phase_ssd_scan(**small)
    (line,) = _phase_lines(capsys)
    assert line["phase"] == "ssd_scan" and line["rel_l2"] == rel
    assert set(rel) == {"y", "dx", "ddt", "da", "db", "dc", "dd"}
    assert max(rel.values()) < chip_smoke.SSD_REL_L2_TOL / 3
    real = S._ssd_bwd

    def wrong_db(*a):
        dx, ddt, dcum, db, dc = real(*a)
        return dx, ddt, dcum, 1.5 * db, dc

    monkeypatch.setattr(S, "_ssd_bwd", wrong_db)
    with pytest.raises(SystemExit) as exc:
        chip_smoke.phase_ssd_scan(**small)
    assert "ssd_scan" in str(exc.value.code) and "db" in str(exc.value.code)


def test_phase_ssd_scan_with_groups_at_a_small_size(chip_smoke, capsys,
                                                    monkeypatch):
    """The probe with B and C in groups, as the nemotron cell runs it, in
    interpret mode: 16 heads of 64 in 2 groups (a head block of 8 a
    group), a state of 16, chunks of 128 over 384 positions, held to
    ``reference/nemotron.py``'s recurrence; and a backward kernel whose dC
    is half again too large fails it."""
    import importlib

    S = importlib.import_module("paddle_tpu.ops.pallas.ssd_scan")
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    small = dict(t=384, heads=16, d_head=64, state=16, chunk=128, groups=2)
    rel = chip_smoke.phase_ssd_scan(**small)
    (line,) = _phase_lines(capsys)
    assert line["phase"] == "ssd_scan" and line["groups"] == 2
    assert max(rel.values()) < chip_smoke.SSD_REL_L2_TOL / 3
    real = S._ssd_bwd

    def wrong_dc(*a):
        dx, ddt, dcum, db, dc = real(*a)
        return dx, ddt, dcum, db, 1.5 * dc

    monkeypatch.setattr(S, "_ssd_bwd", wrong_dc)
    with pytest.raises(SystemExit) as exc:
        chip_smoke.phase_ssd_scan(**small)
    assert "dc" in str(exc.value.code)


def test_phase_grouped_dot_at_a_small_size(chip_smoke, capsys, monkeypatch):
    """The probe of the grouped kernels in interpret mode at a block of
    512 rows, widths 384 and 256, 4 groups: six products, each within
    its tolerance of ragged_dot; and a weights' kernel a third too large
    fails it."""
    import importlib

    G = importlib.import_module("paddle_tpu.ops.pallas.grouped_matmul")
    monkeypatch.setenv("PADDLE_TPU_PALLAS", "interpret")
    small = dict(cells={"small": (512, 384, 256, 4, 100)}, calls=1)
    rel = chip_smoke.phase_grouped_dot(**small)
    lines = _phase_lines(capsys)
    assert [line["product"] for line in lines] == list(rel) == [
        "small %s %s" % (mode, shape) for shape in ("512x384x256",
                                                   "512x256x384")
        for mode in ("forward", "lhs_grad", "rhs_grad")]
    assert max(rel.values()) < chip_smoke.GROUPED_REL_L2_TOL / 3
    assert all(line["tiles"] in line["kernel_ms_by_tiles"] for line in lines)
    real = G._tgmm
    monkeypatch.setattr(G, "_tgmm", lambda *a: real(*a) * 1.3)
    with pytest.raises(SystemExit) as exc:
        chip_smoke.phase_grouped_dot(**small)
    assert "rhs_grad" in str(exc.value.code)
