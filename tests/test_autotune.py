"""Autotune subsystem (ISSUE 6): cache robustness (corrupt/torn files
fall back to defaults with a warning, never a crash), cache-hit
determinism (a second sweep never re-times), the PADDLE_TPU_AUTOTUNE=0
kill switch (hand-set defaults, bit-exact pre-autotune behavior),
threshold decisions, calibration factors feeding the cost model and the
fusion gates, and the flash_min_t resolution order."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import autotune


@pytest.fixture
def tuned(tmp_path, monkeypatch):
    """Point the cache at a fresh temp file and reset in-process state."""
    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_CACHE", path)
    monkeypatch.delenv("PADDLE_TPU_AUTOTUNE", raising=False)
    autotune.reset()
    yield path
    autotune.reset()


class TestCache:
    def test_round_trip(self, tuned):
        sig = autotune.signature("fam", shape=(8, 128), dtype="float32",
                                 backend="cpu")
        assert autotune.lookup(sig) is None
        autotune.record(sig, {"params": {"block": 64}, "measured_ms": 1.5})
        got = autotune.lookup(sig)
        assert got["params"] == {"block": 64}
        # on-disk: versioned schema, atomic file
        with open(tuned) as f:
            data = json.load(f)
        assert data["schema"] == autotune.SCHEMA_VERSION
        assert sig in data["entries"]

    def test_signature_is_canonical(self):
        a = autotune.signature("f", b=2, a=1)
        b = autotune.signature("f", a=1, b=2)
        assert a == b == "f|a=1|b=2"
        assert autotune.signature("f", shape=(4, 8)) == "f|shape=4x8"

    def test_corrupt_cache_falls_back_with_warning(self, tuned):
        with open(tuned, "w") as f:
            f.write('{"schema": 1, "entries": {"x": ')  # torn write
        with pytest.warns(UserWarning, match="unreadable"):
            assert autotune.lookup("anything") is None
        # a record REPAIRS the file rather than crashing on the merge
        autotune.record("s", {"params": {"k": 1}})
        assert autotune.lookup("s")["params"] == {"k": 1}
        with open(tuned) as f:
            json.load(f)  # valid again

    def test_wrong_schema_is_ignored(self, tuned):
        with open(tuned, "w") as f:
            json.dump({"schema": 999, "entries": {"s": {"params": {}}}}, f)
        with pytest.warns(UserWarning):
            assert autotune.lookup("s") is None

    def test_garbage_bytes_do_not_crash(self, tuned):
        with open(tuned, "wb") as f:
            f.write(b"\x00\xff garbage \x7f")
        with pytest.warns(UserWarning):
            assert autotune.entries() == {}

    def test_cache_hit_across_processes(self, tuned):
        """A second PROCESS sees the same winner — the cache is the file,
        not process state."""
        sig = autotune.signature("xproc", k=1, backend="cpu")
        autotune.record(sig, {"params": {"block": 32}})
        out = subprocess.run(
            [sys.executable, "-c",
             "from paddle_tpu import autotune; "
             "print(autotune.lookup(%r)['params']['block'])" % sig],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PADDLE_TPU_AUTOTUNE_CACHE": tuned},
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert out.returncode == 0, out.stderr[-500:]
        assert out.stdout.strip() == "32"

    def test_kill_switch_disables_reads_and_writes(self, tuned,
                                                   monkeypatch):
        sig = autotune.signature("fam", k=1)
        autotune.record(sig, {"params": {"block": 64}})
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "0")
        assert autotune.lookup(sig) is None
        assert autotune.entries() == {}
        autotune.record("other", {"params": {}})  # silently dropped
        monkeypatch.delenv("PADDLE_TPU_AUTOTUNE")
        assert autotune.lookup("other") is None
        assert autotune.lookup(sig) is not None


class TestSweep:
    def test_sweep_times_and_caches_winner(self, tuned):
        import jax.numpy as jnp

        calls = []

        def runner(params):
            calls.append(params["k"])
            return jnp.zeros(()) + params["k"]

        cands = [{"k": 1}, {"k": 2}, {"k": 3}]
        e1 = autotune.sweep("swp", {"shape": (4,)}, cands, runner,
                            repeats=1, warmup=0)
        assert e1["params"]["k"] in (1, 2, 3)
        assert not e1["cached"]
        n_after_first = len(calls)
        assert n_after_first >= 3
        # second run: pure cache hit, runner NEVER invoked again
        e2 = autotune.sweep("swp", {"shape": (4,)}, cands, runner,
                            repeats=1, warmup=0)
        assert e2["cached"] is True
        assert e2["params"] == e1["params"]
        assert len(calls) == n_after_first

    def test_sweep_deterministic_across_reload(self, tuned):
        import jax.numpy as jnp

        e1 = autotune.sweep("det", {}, [{"k": 7}],
                            lambda p: jnp.zeros(()), repeats=1, warmup=0)
        autotune.reset()  # simulate a fresh process: reload from disk
        e2 = autotune.sweep("det", {}, [{"k": 7}],
                            lambda p: jnp.zeros(()), repeats=1, warmup=0)
        assert e2["cached"] and e2["params"] == e1["params"]

    def test_sweep_disabled_returns_first_candidate(self, tuned,
                                                    monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "0")
        e = autotune.sweep("off", {}, [{"k": 9}, {"k": 10}],
                           lambda p: (_ for _ in ()).throw(
                               AssertionError("must not time")))
        assert e["params"] == {"k": 9} and e.get("disabled")

    def test_sweep_records_calibration(self, tuned):
        import jax.numpy as jnp

        e = autotune.sweep("cal", {"s": 1}, [{"k": 1}],
                           lambda p: jnp.zeros(()),
                           baseline=lambda: jnp.zeros(()),
                           predicted_gain=2.0, repeats=1, warmup=0)
        assert "calibration" in e and e["calibration"] > 0
        sig = autotune.sweep_signature("cal", {"s": 1})
        assert autotune.calibration_factor(sig) == pytest.approx(
            e["calibration"])
        assert sig in autotune.calibrations()

    @pytest.mark.slow
    def test_silicon_block_sweep_smoke(self, tuned):
        """The real thing at toy scale: sweep fused-LN block rows with
        actual kernel executions (interpret mode).  Marked slow — the
        tier-1 run stays CPU-fast; the hw watcher runs it on chip."""
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas.fused_ln import fused_dropout_add_ln

        x = jnp.ones((64, 128))
        res = jnp.zeros((64, 128))
        g = jnp.ones(128)
        b = jnp.zeros(128)

        def runner(params):
            os.environ["PADDLE_TPU_FUSED_LN_BLOCK_ROWS"] = \
                str(params["block_rows"])
            try:
                return fused_dropout_add_ln(x, res, g, b)
            finally:
                os.environ.pop("PADDLE_TPU_FUSED_LN_BLOCK_ROWS", None)

        e = autotune.sweep("fused_ln", {"rows": 64, "d": 128},
                           [{"block_rows": 8}, {"block_rows": 64}],
                           runner, repeats=1)
        assert e["params"]["block_rows"] in (8, 64)
        e2 = autotune.sweep("fused_ln", {"rows": 64, "d": 128},
                            [{"block_rows": 8}, {"block_rows": 64}],
                            runner, repeats=1)
        assert e2["cached"]


class TestThresholdDecision:
    def test_decide_threshold_golden(self):
        rows = {128: (2.0, 1.0), 256: (1.5, 1.4), 512: (1.0, 1.5),
                1024: (1.0, 2.1)}
        assert autotune.decide_threshold(rows) == 512

    def test_decide_threshold_no_clean_win(self):
        rows = {128: (2.0, 1.0), 512: (1.0, 1.5), 1024: (3.0, 2.0)}
        assert autotune.decide_threshold(rows) is None

    def test_flash_min_t_resolution_order(self, tuned, monkeypatch):
        from paddle_tpu.ops.pallas.flash_attention import flash_min_t

        monkeypatch.delenv("PADDLE_TPU_FLASH_MIN_T", raising=False)
        assert flash_min_t() == 512            # hand-set default
        autotune.record_flash_min_t(256, rows={256: (1.0, 1.5)})
        assert flash_min_t() == 256            # cached measured decision
        monkeypatch.setenv("PADDLE_TPU_FLASH_MIN_T", "1024")
        assert flash_min_t() == 1024           # env override wins
        monkeypatch.delenv("PADDLE_TPU_FLASH_MIN_T")
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "0")
        assert flash_min_t() == 512            # kill switch -> default


class TestKillSwitchBitExact:
    def test_autotune_off_restores_pre_autotune_train_path(
            self, tuned, monkeypatch):
        """A poisoned cache entry (absurd block rows for the conv-BN
        epilogue) must have NO effect with PADDLE_TPU_AUTOTUNE=0: the
        losses match a run that never had a cache bit-exactly."""
        from paddle_tpu.executor import Scope, scope_guard

        def build():
            fluid.unique_name.switch()
            main, startup = fluid.Program(), fluid.Program()
            with fluid.program_guard(main, startup):
                img = fluid.layers.data(name="img", shape=[8, 16, 16],
                                        dtype="float32")
                label = fluid.layers.data(name="label", shape=[1],
                                          dtype="int64")
                c = fluid.layers.conv2d(img, num_filters=8,
                                        filter_size=3, padding=1,
                                        bias_attr=False)
                h = fluid.layers.batch_norm(c, act="relu")
                pool = fluid.layers.pool2d(h, pool_size=16,
                                           pool_type="avg")
                pred = fluid.layers.fc(pool, size=10, act="softmax")
                loss = fluid.layers.reduce_mean(
                    fluid.layers.cross_entropy(input=pred, label=label))
                fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
            return main, startup, loss

        rng = np.random.RandomState(0)
        feed = {"img": rng.randn(4, 8, 16, 16).astype("float32"),
                "label": rng.randint(0, 10, (4, 1)).astype("int64")}

        def run_steps():
            main, startup, loss = build()
            exe = fluid.Executor()
            with scope_guard(Scope()):
                exe.run(startup)
                return [float(np.asarray(
                    exe.run(main, feed=feed, fetch_list=[loss])[0])
                    .reshape(())) for _ in range(3)]

        baseline = run_steps()
        # poison the cache with a factor that would flip the fusion gate
        # and absurd block params
        sig = autotune.sweep_signature(
            "conv_bn_act", {"shape": (-1, 16, 16, 8),
                            "dtype": "float32", "act": "relu"})
        autotune.record(sig, {"params": {"block_rows": 7},
                              "calibration": 1e-9})
        monkeypatch.setenv("PADDLE_TPU_AUTOTUNE", "0")
        killed = run_steps()
        assert killed == baseline


class TestCostModelExposure:
    def test_bench_json_exposes_calibration_factors(self, tuned):
        autotune.record(
            autotune.signature("conv_bn_act", shape=(1, 2),
                               backend="cpu"),
            {"params": {}, "calibration": 1.7})
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            y = fluid.layers.fc(x, size=2)
        report = main.analyze(targets=[y.name])
        lines = [json.loads(l) for l in
                 report.cost.bench_json().splitlines()]
        cal = [l for l in lines
               if l["metric"] == "autotune_calibration_factors"]
        assert len(cal) == 1
        assert cal[0]["value"] == 1
        assert list(cal[0]["factors"].values()) == [1.7]

    def test_analyze_program_cli_bench_json(self, tuned, tmp_path):
        """analyze_program --bench-json carries the factors end-to-end
        (the CLI is what perf PRs cite)."""
        from paddle_tpu.proto import save_program

        autotune.record(
            autotune.signature("conv_bn_act", shape=(1, 8, 16, 16),
                               dtype="float32", act="relu",
                               backend="cpu"),
            {"params": {}, "calibration": 2.5})
        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            fluid.layers.fc(x, size=2)
        pjson = str(tmp_path / "prog.json")
        save_program(main, pjson)
        bench = str(tmp_path / "bench.txt")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.tools.analyze_program",
             "--program-json", pjson, "--bench-json", bench],
            capture_output=True, text=True, timeout=240,
            env={**os.environ, "JAX_PLATFORMS": "cpu",
                 "PYTHONPATH": repo + os.pathsep
                 + os.environ.get("PYTHONPATH", "")},
            cwd=repo)
        assert out.returncode == 0, (out.stdout + out.stderr)[-800:]
        with open(bench) as f:
            body = f.read()
        assert "autotune_calibration_factors" in body
        line = next(json.loads(l) for l in body.splitlines()
                    if "autotune_calibration_factors" in l)
        assert line["factors"][autotune.signature(
            "conv_bn_act", shape=(1, 8, 16, 16), dtype="float32",
            act="relu", backend="cpu")] == 2.5
