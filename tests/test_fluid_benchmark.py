"""benchmark/fluid_benchmark.py — the reference harness CLI: model
builders wire up and one bench pass produces the reference's
``examples/sed`` report (reference ``benchmark/fluid/
fluid_benchmark.py:296-300``)."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_cli_mnist_cpu_pass():
    """One mnist pass on CPU through the real CLI prints the per-pass
    and total examples/sed lines and exits 0."""
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark",
                                      "fluid_benchmark.py"),
         "--model", "mnist", "--device", "CPU", "--iterations", "4",
         "--batch_size", "16"],
        capture_output=True, text=True, timeout=300, cwd=REPO)
    assert res.returncode == 0, res.stderr[-800:]
    assert "examples/sed" in res.stdout
    assert "Pass: 0" in res.stdout
    assert "Total examples: 64" in res.stdout


def test_build_model_covers_all_workloads():
    """Every --model choice builds a program with a loss var (no
    execution — builder wiring only)."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import importlib

    import jax

    jax.config.update("jax_platforms", "cpu")
    fb = importlib.import_module("fluid_benchmark")

    class A:
        batch_size = 4
        learning_rate = 1e-3
        no_amp = True

    for m in fb.MODELS:
        A.model = m
        main, startup, feed_fn, loss = fb.build_model(A, on_tpu=False)
        assert loss.name in main.global_block().vars
        feed = feed_fn(4)
        assert isinstance(feed, dict) and feed


def test_device_tpu_never_runs_on_another_backend(monkeypatch):
    """``--device TPU`` (the default) on a backend that is not the chip
    exits non-zero before building a model: there is no probe, no notice
    and no switch to the CPU — the user asks for the CPU with
    ``--device CPU``."""
    sys.path.insert(0, os.path.join(REPO, "benchmark"))
    import importlib

    import pytest

    fb = importlib.import_module("fluid_benchmark")
    monkeypatch.setattr(fb, "build_model", lambda *a: pytest.fail(
        "a model was built on a backend the user did not ask for"))
    monkeypatch.setattr(
        sys, "argv",
        ["fluid_benchmark.py", "--model", "mnist", "--iterations", "1"])
    with pytest.raises(SystemExit) as ei:
        fb.main()
    assert "--device TPU but JAX reports platform 'cpu'" in str(ei.value)
