"""Fault-tolerant runtime (ISSUE 2): fault injection, retry/backoff,
atomic+versioned checkpoints with auto-resume, the NaN step-guard, the
resilience lint check, and the chaos CLI acceptance scenario.

Cluster-level kill-and-resume lives in test_fault_tolerance.py (slow);
everything here is single-process and fast."""

import os
import subprocess
import sys
import time
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.executor import Scope, scope_guard
from paddle_tpu.resilience import (checkpoint, faults, guard, retry,
                                   watchdog)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_resilience_state(monkeypatch):
    """Every test starts with an inert injector, fresh guard stats and
    no resilience env knobs leaking in from outside."""
    for var in ("PADDLE_TPU_FAULT_SPEC", "PADDLE_TPU_NAN_GUARD",
                "PADDLE_TPU_FAULT_STATE_FILE",
                "PADDLE_TPU_NAN_GUARD_MAX_SKIPS"):
        monkeypatch.delenv(var, raising=False)
    faults.set_fault_spec("")
    guard.stats.reset()
    yield
    faults.set_fault_spec("")
    guard.stats.reset()


# ---------------------------------------------------------------------------
# fault spec parsing / firing
# ---------------------------------------------------------------------------
class TestFaultSpec:
    def test_parses_kinds_and_params(self):
        inj = faults.FaultInjector(
            "nan_grad@step=3,target=fc_0.w_0@GRAD;"
            "ckpt_write_fail@step=5,times=2;"
            "worker_kill@step=7,rank=1;"
            "io_fail@target=read,p=0.5,seed=9")
        assert [f.kind for f in inj.faults] == [
            "nan_grad", "ckpt_write_fail", "worker_kill", "io_fail"]
        nan = inj.faults[0]
        assert nan.step == 3 and nan.target == "fc_0.w_0@GRAD"
        assert np.isnan(nan.value)
        assert inj.faults[1].times == 2
        assert inj.faults[2].rank == 1
        assert inj.faults[3].site == "io_read"
        assert len(inj.trace_faults) == 1

    def test_rejects_unknown_kind_and_param(self):
        with pytest.raises(ValueError):
            faults.FaultInjector("frobnicate@step=1")
        with pytest.raises(ValueError):
            faults.FaultInjector("nan_grad@wat=1")

    def test_step_and_times_budget(self):
        f = faults.Fault.parse("ckpt_write_fail@step=5,times=2")
        assert not f.should_fire(4, 0)
        assert f.should_fire(5, 0)
        assert f.should_fire(5, 0)
        assert not f.should_fire(5, 0)  # budget spent

    def test_probabilistic_fire_is_seeded(self):
        f1 = faults.Fault.parse("io_fail@p=0.5,seed=11,times=0")
        f2 = faults.Fault.parse("io_fail@p=0.5,seed=11,times=0")
        draws1 = [f1.should_fire(k, 0) for k in range(20)]
        draws2 = [f2.should_fire(k, 0) for k in range(20)]
        assert draws1 == draws2
        assert any(draws1) and not all(draws1)

    def test_rank_scoping(self):
        f = faults.Fault.parse("worker_kill@step=2,rank=1")
        assert not f.should_fire(2, 0)
        assert f.should_fire(2, 1)

    def test_site_fault_raises_transient(self):
        inj = faults.FaultInjector("compile_fail@times=1")
        with pytest.raises(faults.TransientFault):
            inj.maybe_fire("compile")
        inj.maybe_fire("compile")  # budget spent: no raise

    def test_state_file_spans_restarts(self, tmp_path):
        state = str(tmp_path / "fault_state.json")
        inj = faults.FaultInjector("worker_kill@step=7", state_file=state)
        assert inj.faults[0].should_fire(7, 0)
        inj._persist_state()
        # a "restarted" injector sees the budget already consumed
        inj2 = faults.FaultInjector("worker_kill@step=7",
                                    state_file=state)
        assert inj2.faults[0].exhausted()
        assert not inj2.faults[0].should_fire(7, 0)


# ---------------------------------------------------------------------------
# retry / timeout / backoff
# ---------------------------------------------------------------------------
class TestRetry:
    def test_succeeds_after_transient_failures(self):
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise faults.TransientFault("boom")
            return "ok"

        policy = retry.RetryPolicy(max_attempts=4, base_delay=0.001)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            assert retry.retry_call(flaky, policy=policy) == "ok"
        assert calls["n"] == 3

    def test_non_retryable_propagates_immediately(self):
        calls = {"n": 0}

        def bug():
            calls["n"] += 1
            raise TypeError("a real bug")

        with pytest.raises(TypeError):
            retry.retry_call(bug, policy=retry.RetryPolicy(
                max_attempts=5, base_delay=0.001))
        assert calls["n"] == 1

    def test_exhaustion_raises_with_last_error(self):
        def always():
            raise OSError("disk on fire")

        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            with pytest.raises(retry.RetryExhaustedError) as ei:
                retry.retry_call(always, policy=retry.RetryPolicy(
                    max_attempts=2, base_delay=0.001))
        assert isinstance(ei.value.last_error, OSError)
        assert ei.value.attempts == 2

    def test_backoff_schedule_deterministic_and_bounded(self):
        p = retry.RetryPolicy(max_attempts=5, base_delay=0.1,
                              max_delay=0.3, jitter=0.25, seed=4)
        d1, d2 = list(p.delays()), list(p.delays())
        assert d1 == d2 and len(d1) == 4
        # exponential up to the (jittered) ceiling
        assert all(d <= 0.3 * 1.25 + 1e-9 for d in d1)
        assert d1[1] > d1[0]

    def test_run_with_timeout(self):
        assert retry.run_with_timeout(lambda: 42, 5.0) == 42
        with pytest.raises(TimeoutError):
            retry.run_with_timeout(lambda: time.sleep(10), 0.2,
                                   what="nap")
        with pytest.raises(watchdog.WorkerLostError):
            retry.run_with_timeout(lambda: time.sleep(10), 0.2,
                                   what="barrier",
                                   error_cls=watchdog.WorkerLostError)


# ---------------------------------------------------------------------------
# shared tiny model
# ---------------------------------------------------------------------------
def _build_model(lr=0.1, opt="adam"):
    fluid.unique_name.switch()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[4], dtype="float32")
        y = fluid.layers.data("y", shape=[1], dtype="float32")
        h = fluid.layers.fc(x, size=8, act="relu")
        p = fluid.layers.fc(h, size=1)
        loss = fluid.layers.reduce_mean(fluid.layers.square(p - y))
        factory = (fluid.optimizer.Adam if opt == "adam"
                   else fluid.optimizer.SGD)
        factory(learning_rate=lr).minimize(loss)
    return main, startup, loss


def _make_batches(n, bs=16, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(bs, 4).astype("float32"),
             rng.randn(bs, 1).astype("float32")) for _ in range(n)]


def _persistable_values(program):
    sc = fluid.global_scope()
    out = {}
    for v in program.list_vars():
        if v.persistable and sc.get(v.name) is not None:
            out[v.name] = np.asarray(sc.get(v.name))
    return out


# ---------------------------------------------------------------------------
# atomic io.py (satellite)
# ---------------------------------------------------------------------------
class TestAtomicIO:
    def _save_one(self, tmp_path):
        main, startup, loss = _build_model(opt="sgd")
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            fluid.io.save_persistables(exe, str(tmp_path), main)
        return main

    def test_failed_save_leaves_no_torn_output(self, tmp_path,
                                               monkeypatch):
        main = self._save_one(tmp_path)
        before = {}
        for f in os.listdir(str(tmp_path)):
            p = os.path.join(str(tmp_path), f)
            with open(p, "rb") as fh:
                before[f] = fh.read()
        assert before

        def torn_save(f, arr, **kw):
            # write garbage bytes then die: simulates a mid-write crash
            f.write(b"\x93NUMPY-GARBAGE")
            raise OSError("injected torn write")

        monkeypatch.setattr(np, "save", torn_save)
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            sc = fluid.global_scope()
            sc.set("fc_0.w_0", np.zeros([4, 8], "float32"))
            with pytest.raises(OSError, match="torn write"):
                fluid.io.save_vars(
                    exe, str(tmp_path), main,
                    vars=[main.global_block().var("fc_0.w_0")])
        # no tmp litter, and every pre-existing file is byte-identical
        assert sorted(os.listdir(str(tmp_path))) == sorted(before)
        for f, data in before.items():
            with open(os.path.join(str(tmp_path), f), "rb") as fh:
                assert fh.read() == data, f

    def test_corrupt_npy_load_names_file_and_var(self, tmp_path):
        main = self._save_one(tmp_path)
        victim_var = "fc_0.w_0"
        victim = os.path.join(str(tmp_path), victim_var + ".npy")
        with open(victim, "wb") as f:
            f.write(b"\x93NUMPY truncated")
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            with pytest.raises(RuntimeError) as ei:
                fluid.io.load_persistables(exe, str(tmp_path), main)
        assert victim_var in str(ei.value)
        assert "corrupt" in str(ei.value) or "unreadable" in str(ei.value)

    def test_missing_combined_npz_is_clear_error(self, tmp_path):
        main, startup, _ = _build_model(opt="sgd")
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            with pytest.raises(RuntimeError) as ei:
                fluid.io.load_persistables(exe, str(tmp_path), main,
                                           filename="nope")
        assert "nope" in str(ei.value)


# ---------------------------------------------------------------------------
# atomic + versioned checkpoints (tentpole)
# ---------------------------------------------------------------------------
class TestVersionedCheckpoint:
    def _train_and_checkpoint(self, root, steps=4, retain=3):
        main, startup, loss = _build_model()
        exe = fluid.Executor(fluid.CPUPlace())
        digests = {}
        with scope_guard(Scope()):
            exe.run(startup)
            for k, (xb, yb) in enumerate(_make_batches(steps)):
                exe.run(main, feed={"x": xb, "y": yb},
                        fetch_list=[loss])
                checkpoint.save_checkpoint(
                    exe, root, main_program=main, step=k,
                    state={"next_step": k + 1}, retain=retain)
                digests[k] = _persistable_values(main)
        return main, startup, loss, digests

    def test_versioning_and_retention(self, tmp_path):
        root = str(tmp_path)
        self._train_and_checkpoint(root, steps=5, retain=3)
        assert [s for s, _ in checkpoint.list_checkpoints(root)] \
            == [4, 3, 2]
        # no staging litter
        assert not [d for d in os.listdir(root)
                    if d.startswith(".tmp-")]

    def test_resume_restores_exact_values_and_state(self, tmp_path):
        root = str(tmp_path)
        main, startup, loss, digests = self._train_and_checkpoint(root)
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            info = checkpoint.try_load_latest_checkpoint(
                exe, root, main_program=main)
            assert info.step == 3
            assert info.state == {"next_step": 4}
            restored = _persistable_values(main)
        for name, want in digests[3].items():
            np.testing.assert_array_equal(restored[name], want)

    def test_checksum_tamper_skips_to_older_valid_version(self, tmp_path):
        root = str(tmp_path)
        main, startup, loss, digests = self._train_and_checkpoint(root)
        newest = checkpoint.list_checkpoints(root)[0][1]
        vars_dir = os.path.join(newest, checkpoint.VARS_SUBDIR)
        victim = sorted(f for f in os.listdir(vars_dir)
                        if f.endswith(".npy"))[0]
        with open(os.path.join(vars_dir, victim), "r+b") as f:
            f.seek(8)
            f.write(b"\xde\xad\xbe\xef")
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                info = checkpoint.try_load_latest_checkpoint(
                    exe, root, main_program=main)
            assert info.step == 2  # newest (3) was tampered: skipped
            restored = _persistable_values(main)
        assert any("checksum" in str(w.message) or "skipping" in
                   str(w.message) for w in caught)
        for name, want in digests[2].items():
            np.testing.assert_array_equal(restored[name], want)

    def test_manifestless_dir_never_loads(self, tmp_path):
        root = str(tmp_path)
        main, startup, loss, _ = self._train_and_checkpoint(root,
                                                            steps=2)
        # fake a torn version that looks newest but has no manifest
        torn = os.path.join(root, "%s%08d" % (checkpoint.CKPT_PREFIX, 99))
        os.makedirs(os.path.join(torn, checkpoint.VARS_SUBDIR))
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                info = checkpoint.try_load_latest_checkpoint(
                    exe, root, main_program=main)
        assert info.step == 1

    def test_all_corrupt_returns_none(self, tmp_path):
        root = str(tmp_path / "empty")
        main, startup, _ = _build_model()
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            assert checkpoint.try_load_latest_checkpoint(
                exe, root, main_program=main) is None

    def test_transient_write_failure_is_retried(self, tmp_path):
        faults.set_fault_spec("ckpt_write_fail@times=2")
        root = str(tmp_path)
        main, startup, loss = _build_model()
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                path = checkpoint.save_checkpoint(
                    exe, root, main_program=main, step=0,
                    policy=retry.RetryPolicy(max_attempts=4,
                                             base_delay=0.001))
        assert path is not None and os.path.isdir(path)
        assert sum("retrying" in str(w.message) for w in caught) == 2
        checkpoint.verify_checkpoint(path)  # intact despite the faults

    def test_write_retries_exhausted_raises(self, tmp_path):
        faults.set_fault_spec("ckpt_write_fail@times=0")  # unlimited
        main, startup, _ = _build_model()
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                with pytest.raises(retry.RetryExhaustedError):
                    checkpoint.save_checkpoint(
                        exe, str(tmp_path), main_program=main, step=0,
                        policy=retry.RetryPolicy(max_attempts=2,
                                                 base_delay=0.001))
        # a failed save leaves neither a version nor staging litter
        assert checkpoint.list_checkpoints(str(tmp_path)) == []


# ---------------------------------------------------------------------------
# NaN/Inf step-guard (tentpole)
# ---------------------------------------------------------------------------
class TestNanGuard:
    def _run(self, batches, spec="", skip=(), guard_on=True,
             monkeypatch=None):
        if guard_on:
            monkeypatch.setenv("PADDLE_TPU_NAN_GUARD", "1")
        faults.set_fault_spec(spec)
        guard.stats.reset()
        main, startup, loss = _build_model()
        exe = fluid.Executor(fluid.CPUPlace())
        losses = []
        with scope_guard(Scope()):
            exe.run(startup)
            for k, (xb, yb) in enumerate(batches):
                if k in skip:
                    continue
                faults.set_step(k)
                with warnings.catch_warnings():
                    warnings.simplefilter("ignore")
                    (lv,) = exe.run(main, feed={"x": xb, "y": yb},
                                    fetch_list=[loss])
                losses.append(float(np.asarray(lv).reshape(())))
            params = _persistable_values(main)
        return losses, params, guard.stats.as_dict()

    def test_nan_grad_step_skipped_and_counted(self, monkeypatch):
        batches = _make_batches(5)
        _, params, stats = self._run(batches, spec="nan_grad@step=2",
                                     monkeypatch=monkeypatch)
        assert stats["skipped_steps"] == 1
        assert stats["last_skipped_step"] == 2
        # trajectory == the same step (its gate feed cold: step 2, the
        # fault's, never comes) in a run that never applied step 2
        _, oracle, _ = self._run(batches, spec="nan_grad@step=2",
                                 skip={2}, monkeypatch=monkeypatch)
        for name in params:
            np.testing.assert_array_equal(params[name], oracle[name])

    def test_inf_targeted_grad_also_skips(self, monkeypatch):
        batches = _make_batches(4)
        _, params, stats = self._run(
            batches, spec="inf_grad@step=1,target=fc_1.w_0@GRAD",
            monkeypatch=monkeypatch)
        assert stats["skipped_steps"] == 1
        for v in params.values():
            assert np.isfinite(v).all()

    def test_unguarded_nan_poisons_params(self, monkeypatch):
        # negative control: without the guard the same fault corrupts
        batches = _make_batches(3)
        _, params, _ = self._run(batches, spec="nan_grad@step=1",
                                 guard_on=False, monkeypatch=monkeypatch)
        assert any(not np.isfinite(v).all() for v in params.values())

    def test_consecutive_skip_limit_aborts(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_NAN_GUARD", "1")
        monkeypatch.setenv("PADDLE_TPU_NAN_GUARD_MAX_SKIPS", "3")
        faults.set_fault_spec("nan_grad@times=0")  # every step
        guard.stats.reset()
        main, startup, loss = _build_model()
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                with pytest.raises(RuntimeError, match="diverged"):
                    for k, (xb, yb) in enumerate(_make_batches(6)):
                        faults.set_step(k)
                        exe.run(main, feed={"x": xb, "y": yb},
                                fetch_list=[loss])
        assert guard.stats.consecutive_skips == 3

    def test_guard_covers_data_parallel_path(self, monkeypatch):
        """SPMDRunner (CompiledProgram.with_data_parallel) carries the
        guard too — the DP trainer is where survival matters most."""
        monkeypatch.setenv("PADDLE_TPU_NAN_GUARD", "1")
        faults.set_fault_spec("")
        guard.stats.reset()
        main, startup, loss = _build_model()
        cp = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            for xb, yb in _make_batches(2):
                (lv,) = exe.run(cp, feed={"x": xb, "y": yb},
                                fetch_list=[loss])
                assert np.isfinite(np.asarray(lv)).all()
        assert guard.stats.total_steps == 2
        assert guard.stats.skipped_steps == 0


# ---------------------------------------------------------------------------
# resilience lint check (satellite)
# ---------------------------------------------------------------------------
class TestResilienceLint:
    def test_unguarded_training_program_advisory(self):
        from paddle_tpu.static_analysis import Severity, verify_program

        main, startup, loss = _build_model()
        diags = verify_program(main, targets=[loss.name])
        hits = [d for d in diags if d.check == "resilience-finite-guard"]
        assert hits and hits[0].severity is Severity.INFO
        assert "PADDLE_TPU_NAN_GUARD" in hits[0].hint

    def test_guarded_program_is_clean(self):
        from paddle_tpu.static_analysis import verify_program

        main, startup, loss = _build_model()
        main._nan_guard = True
        diags = verify_program(main, targets=[loss.name])
        assert not [d for d in diags
                    if d.check == "resilience-finite-guard"]

    def test_inference_program_is_exempt(self):
        from paddle_tpu.static_analysis import verify_program

        fluid.unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[4], dtype="float32")
            out = fluid.layers.fc(x, size=2)
        diags = verify_program(main, targets=[out.name])
        assert not [d for d in diags
                    if d.check == "resilience-finite-guard"]


# ---------------------------------------------------------------------------
# watchdog / heartbeats
# ---------------------------------------------------------------------------
class TestWatchdog:
    def test_wait_cluster_detects_dead_worker_quickly(self):
        sleeper = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(60)"])
        dier = subprocess.Popen(
            [sys.executable, "-c", "import sys; sys.exit(5)"])
        t0 = time.time()
        try:
            with pytest.raises(watchdog.WorkerLostError) as ei:
                watchdog.wait_cluster([sleeper, dier], timeout=30,
                                      poll=0.1)
        finally:
            for p in (sleeper, dier):
                if p.poll() is None:
                    p.kill()
                p.wait()
        assert time.time() - t0 < 20  # bounded, nowhere near the hang
        assert 5 in ei.value.returncodes
        assert sleeper.poll() is not None  # survivor was reaped

    def test_wait_cluster_timeout_raises(self):
        p = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(60)"])
        try:
            with pytest.raises(watchdog.WorkerLostError,
                               match="timeout"):
                watchdog.wait_cluster([p], timeout=0.5, poll=0.1)
        finally:
            if p.poll() is None:
                p.kill()
            p.wait()

    def test_wait_cluster_all_ok(self):
        procs = [subprocess.Popen([sys.executable, "-c", "pass"])
                 for _ in range(2)]
        assert watchdog.wait_cluster(procs, timeout=30) == [0, 0]

    def test_heartbeat_staleness(self, tmp_path):
        hb_dir = str(tmp_path)
        writer = watchdog.HeartbeatWriter(hb_dir, rank=1, interval=0.1)
        writer.beat()
        mon = watchdog.HeartbeatMonitor(hb_dir, ranks=[1], timeout=0.5,
                                        boot_grace=0.1)
        assert mon.check() is True
        # age the heartbeat past the timeout: rank declared lost
        stale_t = time.time() - 5.0
        os.utime(os.path.join(hb_dir, "hb-1"), (stale_t, stale_t))
        with pytest.raises(watchdog.WorkerLostError) as ei:
            mon.check()
        assert ei.value.ranks == (1,)

    def test_clean_shutdown_is_not_worker_loss(self, tmp_path):
        """A peer that STOPPED (done marker) is finished, not lost — a
        slower survivor must not be hard-exited for outliving it."""
        hb_dir = str(tmp_path)
        w = watchdog.HeartbeatWriter(hb_dir, rank=1,
                                     interval=0.05).start()
        mon = watchdog.HeartbeatMonitor(hb_dir, ranks=[1], timeout=0.3,
                                        boot_grace=0.1)
        assert mon.check() is True
        w.stop()  # clean shutdown writes hb-1.done
        time.sleep(0.6)  # well past the staleness timeout
        assert mon.check() is True

    def test_heartbeat_writer_keeps_beating(self, tmp_path):
        hb_dir = str(tmp_path)
        with watchdog.HeartbeatWriter(hb_dir, rank=0, interval=0.05):
            mon = watchdog.HeartbeatMonitor(hb_dir, ranks=[0],
                                            timeout=1.0)
            time.sleep(0.3)
            assert mon.check() is True


# ---------------------------------------------------------------------------
# executor-level site faults
# ---------------------------------------------------------------------------
class TestExecutorRetry:
    def test_transient_compile_failure_is_retried(self, monkeypatch):
        faults.set_fault_spec("compile_fail@times=1")
        main, startup, loss = _build_model()
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            with warnings.catch_warnings(record=True) as caught:
                warnings.simplefilter("always")
                exe.run(startup)
                xb, yb = _make_batches(1)[0]
                (lv,) = exe.run(main, feed={"x": xb, "y": yb},
                                fetch_list=[loss])
        assert np.isfinite(np.asarray(lv)).all()
        assert any("retrying" in str(w.message) for w in caught)


# ---------------------------------------------------------------------------
# the chaos CLI — the ISSUE-2 acceptance scenario end to end
# ---------------------------------------------------------------------------
class TestChaosCLI:
    def test_acceptance_scenario_recovers(self, tmp_path):
        """NaN-grad @3 (skipped), transient ckpt-write failure @5
        (retried), worker kill @7 (restart + auto-resume): final params
        must match the fault-free trajectory bit-for-bit."""
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
        env.pop("PADDLE_TPU_FAULT_SPEC", None)
        res = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.tools.chaos",
             "--steps", "9", "--ckpt-dir", str(tmp_path / "ckpt"),
             "--spec",
             "nan_grad@step=3;ckpt_write_fail@step=5;worker_kill@step=7"],
            capture_output=True, text=True, timeout=300, env=env,
            cwd=REPO)
        assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-800:]
        assert "chaos: PASS" in res.stdout
        assert "skipped steps=[3]" in res.stdout
        assert "resumes=[7]" in res.stdout

    def test_hang_is_bounded_and_recovered(self, tmp_path):
        """An injected hang trips the per-incarnation timeout; the
        restarted worker resumes and finishes."""
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
        env.pop("PADDLE_TPU_FAULT_SPEC", None)
        res = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.tools.chaos",
             "--steps", "5", "--ckpt-dir", str(tmp_path / "ckpt"),
             "--worker-timeout", "15",
             "--spec", "worker_hang@step=2,secs=600"],
            capture_output=True, text=True, timeout=300, env=env,
            cwd=REPO)
        assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-800:]
        assert "rc=timeout" in res.stdout
        assert "chaos: PASS" in res.stdout
