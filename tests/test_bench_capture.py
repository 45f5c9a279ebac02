"""bench.py's orchestrator and cross-checks: no chip means no metric and
a non-zero exit; one record per metric; the peak table refuses devices it
does not know."""

import importlib.util
import os
import types

import pytest


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench_mod", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_xla_cost_analysis_counts_scan_body_once():
    """The MFU cross-check (bench.py _xla_flops_per_step) treats XLA's
    cost-analysis flops as per-step even under the
    num_iteration_per_run scan wrapper, because XLA counts a
    while/scan body ONCE regardless of trip count.  This pins that
    backend behavior: if a jax upgrade starts multiplying by the trip
    count, the cross-check must go back to dividing (the r05 ipr25
    hardware capture read 25x low under an erroneous /iters)."""
    import jax
    import jax.numpy as jnp

    def step(x):
        return x @ x

    @jax.jit
    def one(x):
        return step(x)

    @jax.jit
    def scan4(x):
        c, _ = jax.lax.scan(lambda c, _: (step(c), None), x, None,
                            length=4)
        return c

    x = jnp.ones((64, 64), jnp.float32)

    def flops(f):
        ca = f.lower(x).compile().cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        return float(ca.get("flops", 0.0))

    f1, f4 = flops(one), flops(scan4)
    assert f1 > 0
    assert abs(f4 - f1) / f1 < 0.05, (f1, f4)


def test_dedupe_metrics_one_record_per_metric_last_wins(bench):
    """Satellite (ISSUE 6): the train children print each *_per_chip
    metric twice (measured line first, MFU-enriched re-print after the
    AOT cross-check) — the orchestrator must emit ONE record per metric,
    the LAST (enriched) one, at the first occurrence's position, with
    non-metric lines passing through."""
    plain = {"metric": "resnet50_imagenet_train_images_per_sec_per_chip",
             "value": 2000.0, "unit": "images/sec/chip"}
    enriched = dict(plain, mfu_analytic=0.25, mfu_xla=0.38)
    other = {"metric": "other_metric", "value": 1}
    marker = {"compiled": True}
    out = bench._dedupe_metrics([plain, marker, other, enriched])
    assert out == [enriched, marker, other]
    # a clean single emission is untouched
    assert bench._dedupe_metrics([plain, other]) == [plain, other]
    # duplicate-free input of N metrics stays N records
    assert len([l for l in out if l.get("metric")]) == 2


def test_main_exits_nonzero_and_prints_no_metric_without_a_chip(
        bench, monkeypatch, capsys):
    """The probe reports the CPU backend: no child may run, nothing may be
    printed to stdout, and the exit code is non-zero (the CPU smoke arm
    and the replay of earlier captures are gone)."""
    ran = []

    def run_child(mode, timeout_s):
        ran.append(mode)
        return True, [{"probe": "ok", "platform": "cpu",
                       "device_kind": "cpu", "n_devices": 1}], ""

    monkeypatch.setattr(bench, "_run_child", run_child)
    assert bench.main() != 0
    assert ran == ["probe"]
    assert capsys.readouterr().out == ""


def test_main_exits_nonzero_when_a_chip_child_fails(bench, monkeypatch,
                                                   capsys):
    flagship = {"metric": bench.FLAGSHIP_METRIC, "value": 1.0}

    def run_child(mode, timeout_s):
        if mode == "probe":
            return True, [{"probe": "ok", "platform": "tpu"}], ""
        if mode == "bert":
            return True, [flagship], ""
        if mode == "resnet":
            return False, [], "rc=1 boom"
        return True, [], ""

    monkeypatch.setattr(bench, "_run_child", run_child)
    assert bench.main() != 0
    out = capsys.readouterr().out
    assert "# resnet bench failed: rc=1 boom" in out
    assert out.strip().splitlines()[-1].startswith('{"metric": "%s"'
                                                   % bench.FLAGSHIP_METRIC)


@pytest.mark.parametrize("kind,peak", [
    ("TPU v5 lite", 197e12), ("TPU v5e", 197e12), ("TPU v4", 275e12),
    ("cpu", None), ("TPU v9000", None),
])
def test_peak_flops_refuses_unknown_devices(bench, kind, peak):
    dev = types.SimpleNamespace(device_kind=kind)
    if peak is None:
        with pytest.raises(ValueError, match="no bf16 peak known"):
            bench.peak_flops(dev)
    else:
        assert bench.peak_flops(dev) == peak


@pytest.mark.parametrize("child,args", [
    ("child_bert", (128,)), ("child_bert", (512,)), ("child_resnet", ()),
    ("child_infer", ()), ("child_bert_infer", ()), ("child_ctr", ()),
])
def test_chip_children_refuse_the_cpu_backend(bench, capsys, child, args):
    """No BERT_TINY, no ``*_smoke_*`` metric: a chip child on another
    backend exits before it builds or prints anything."""
    with pytest.raises(SystemExit) as exc:
        getattr(bench, child)(*args)
    assert "refusing to run" in str(exc.value.code)
    assert capsys.readouterr().out == ""
