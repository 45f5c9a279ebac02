"""CI sweep (ISSUE 3 satellite): run the whole-program static analyzer
over every program built in ``examples/`` and require zero ERROR
diagnostics — analyzer regressions and example rot both fail fast,
and every example gets a static cost baseline for free.

Each example module exposes a ``build_program()``-style builder (the
``main()`` entry uses the same builder, so the analyzed program IS the
example's program).  ``long_context_ring.py`` is pure-jax (no Program)
and ``deepfm_ctr.py`` builds via dataset-file readers; they have no
static program to sweep.
"""

import os
import sys

import pytest

import paddle_tpu as fluid

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")
if EXAMPLES not in sys.path:
    sys.path.insert(0, EXAMPLES)


def _mnist():
    import mnist_train

    main, startup, test_prog, loss, acc = mnist_train.build_program()
    return [(main, [loss.name, acc.name]), (test_prog, [acc.name]),
            (startup, None)]


def _bert_tiny():
    import bert_pretrain

    main, startup, feeds, loss = bert_pretrain.build_program(
        tiny=True, seq_len=32)
    return [(main, [loss.name]), (startup, None)]


def _ctr():
    import ps_migration

    main, startup, loss = ps_migration.build_ctr(vocab=512)
    return [(main, [loss.name]), (startup, None)]


def _resnet_eval():
    import resnet_infer

    main, startup, prob = resnet_infer.build_program()
    return [(main, [prob.name]), (startup, None)]



def _gpt_small():
    import gpt_small

    main, startup, feeds, tokens, gen_len = gpt_small.build_program(
        batch=2, prompt_len=8, max_new_tokens=4)
    return [(main, [tokens.name, gen_len.name]), (startup, None)]


def _slim():
    import slim_compress

    main, startup, loss, acc, prob = slim_compress.build_program()
    return [(main, [loss.name, acc.name]), (startup, None)]


@pytest.mark.parametrize("builder", [
    _mnist, _bert_tiny, _ctr, _resnet_eval, _slim, _gpt_small,
], ids=["mnist", "bert-tiny", "ctr", "resnet-eval", "slim",
        "gpt-small"])
def test_every_example_program_analyzes_clean(builder):
    fluid.unique_name.switch()
    for program, targets in builder():
        report = program.analyze(targets=targets)
        assert report.ok, "\n".join(str(d) for d in report.errors)


@pytest.fixture
def empty_autotune_cache(monkeypatch, tmp_path):
    """A cache file of this test's own: the suite's per-process file
    (conftest.py) keeps the calibration entries other test files record
    (test_observability's drift factor, test_planner's planner factor),
    and ``bench_json`` prints a line for them."""
    from paddle_tpu import autotune

    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    autotune.reset()
    yield
    autotune.reset()


def test_example_cost_baselines_are_nonzero(empty_autotune_cache):
    """The BENCH-style static baseline a perf PR would cite: the mnist
    training program has real FLOP/byte totals and a peak estimate."""
    import mnist_train

    fluid.unique_name.switch()
    main, startup, test_prog, loss, acc = mnist_train.build_program()
    report = main.analyze(targets=[loss.name], batch_size=64)
    assert report.cost.total_flops > 1_000_000  # 784->200->200->10 MLP
    assert report.cost.peak_memory_bytes > report.cost.persistent_bytes
    assert report.cost.persistent_bytes > 0
    lines = report.cost.bench_json().splitlines()
    assert len(lines) == 7
    import json as _json

    metrics = {_json.loads(l)["metric"] for l in lines}
    # the async-dispatch additions ride in the same BENCH stream
    assert "static_host_sync_points" in metrics
    assert "static_dispatch_overhead_ms" in metrics

@pytest.mark.parametrize("builder", [
    _mnist, _bert_tiny, _ctr, _resnet_eval, _slim, _gpt_small,
], ids=["mnist", "bert-tiny", "ctr", "resnet-eval", "slim",
        "gpt-small"])
def test_every_example_fuses_and_analyzes_clean(builder):
    """ISSUE 5 CI sweep: the fusion pipeline (on, default config) over
    every example program must introduce ZERO new ERROR diagnostics —
    the fused ops are first-class citizens of the analyzer (cost rules,
    sharding transfers, schedule extraction) and every rewrite is
    verify_pass-bracketed."""
    from paddle_tpu.static_analysis import fusion

    fluid.unique_name.switch()
    for program, targets in builder():
        fused, report = fusion.resolve_fused_program(
            program, targets=targets or ())
        analysis = fused.analyze(targets=targets)
        assert analysis.ok, "\n".join(str(d) for d in analysis.errors)


@pytest.mark.parametrize("builder", [
    _mnist, _bert_tiny, _ctr, _resnet_eval, _slim, _gpt_small,
], ids=["mnist", "bert-tiny", "ctr", "resnet-eval", "slim",
        "gpt-small"])
def test_every_example_program_concurrency_clean(builder):
    """ISSUE 10 CI sweep: the concurrency battery at max_in_flight=2
    finds ZERO races across every example program — training programs
    fetch temporaries (loss/acc), never the donated parameter buffers,
    so the corpus is the precision baseline for the race rules."""
    fluid.unique_name.switch()
    for program, targets in builder():
        report = program.analyze(targets=targets, concurrency=True,
                                 max_in_flight=2)
        assert report.ok, "\n".join(str(d) for d in report.errors)
        assert report.concurrency is not None
        assert report.concurrency.race_free, "\n".join(
            str(d) for d in report.concurrency.races)


def test_dist_worker_sets_concurrency_clean():
    """Every transpiled multi-worker program set (pipeline, DP at 2 and
    8 ranks, MoE) stays race-free at depth 2 — collective rewrites must
    not put a fetched var into a donated buffer."""
    TESTS = os.path.dirname(os.path.abspath(__file__))
    if TESTS not in sys.path:
        sys.path.insert(0, TESTS)
    import dist_model

    sets = []
    workers, _, loss = dist_model.build_pipeline_workers()
    sets.append((workers, loss))
    workers, _, loss = dist_model.build_dp_workers(nranks=2)
    sets.append((workers, loss))
    w0, _, loss = dist_model.build_example_dp_workers("bert", nranks=8)
    sets.append(([w0], loss))
    workers, _, out = dist_model.build_moe_workers(nranks=2)
    sets.append((workers, out))
    for workers, fetch in sets:
        for w in workers:
            # pipeline stages that don't produce the fetch var analyze
            # without it (the split keeps the var declaration in every
            # stage, but only one stage's ops define it)
            has = any(fetch in op.output_arg_names
                      for b in w.blocks for op in b.ops)
            report = w.analyze(targets=[fetch] if has else None,
                               concurrency=True, max_in_flight=2)
            assert report.ok, "\n".join(str(d) for d in report.errors)
            assert report.concurrency.race_free, "\n".join(
                str(d) for d in report.concurrency.races)


def test_fusion_families_fire_across_example_corpus():
    """The rewrite families fire somewhere in the examples: mnist
    carries bias_act + softmax_xent, bert carries the dropout_add_ln
    sites (and attention once T reaches the flash threshold —
    exercised in test_fusion.py)."""
    from paddle_tpu.static_analysis import fusion

    seen = {}
    fluid.unique_name.switch()
    for build in (_mnist, _bert_tiny):
        for program, targets in build():
            _, report = fusion.resolve_fused_program(
                program, targets=targets or ())
            for fam, n in report.counts().items():
                seen[fam] = seen.get(fam, 0) + n
    assert seen.get("bias_act", 0) >= 2
    assert seen.get("softmax_xent", 0) >= 1
    assert seen.get("dropout_add_ln", 0) >= 5
