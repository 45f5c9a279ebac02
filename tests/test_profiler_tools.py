"""Profiler + tools tests (reference: unittests/test_profiler.py and the
API-freeze CI check tools/diff_api.py)."""

import json
import os
import subprocess
import sys

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.executor import Scope, scope_guard

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestProfiler:
    def _run_some_steps(self):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data("x", shape=[8], dtype="float32")
            y = fluid.layers.fc(x, size=4)
            loss = fluid.layers.reduce_mean(y)
            fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            for _ in range(3):
                exe.run(main, feed={"x": np.ones((2, 8), "float32")},
                        fetch_list=[loss])

    def test_host_events_and_chrome_trace(self, tmp_path, capsys):
        trace = str(tmp_path / "profile.json")
        profiler.start_profiler(state="CPU")
        with profiler.record_event("user_scope"):
            self._run_some_steps()
        profiler.stop_profiler(sorted_key="total", profile_path=trace)
        out = capsys.readouterr().out
        assert "Profiling Report" in out
        assert "executor.step" in out
        assert "user_scope" in out

        with open(trace) as f:
            t = json.load(f)
        names = {ev["name"] for ev in t["traceEvents"]}
        # the step's phases are rows of the host-event table
        assert {"user_scope", "executor.step", "executor.compile",
                "executor.feed_stage", "executor.dispatch",
                "host.sync"} <= names
        # unified export: host/span events are X (complete) with real
        # durations; the tracing merge may add metadata (M) rows and
        # flow arrows (s/f) for cross-thread/rank causality
        for ev in t["traceEvents"]:
            assert ev["ph"] in ("X", "M", "s", "f")
            if ev["ph"] == "X":
                assert ev["dur"] >= 0

    def test_profiler_context_manager(self, tmp_path):
        trace = str(tmp_path / "p.json")
        with profiler.profiler(state="CPU", profile_path=trace):
            with profiler.record_event("inner"):
                pass
        assert os.path.exists(trace)
        assert not profiler.is_profiler_enabled()

    def test_record_event_noop_when_disabled(self):
        profiler.reset_profiler()
        with profiler.record_event("not_recorded"):
            pass
        profiler.start_profiler(state="CPU")
        profiler.stop_profiler(profile_path=None)


class TestTimelineTool:
    def test_merge(self, tmp_path):
        p0 = str(tmp_path / "p0.json")
        p1 = str(tmp_path / "p1.json")
        for p, nm in ((p0, "a"), (p1, "b")):
            with open(p, "w") as f:
                json.dump({"traceEvents": [
                    {"name": nm, "ph": "X", "pid": 0, "tid": 1,
                     "ts": 0, "dur": 5}]}, f)
        out = str(tmp_path / "timeline.json")
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "timeline.py"),
             "--profile_path", "h0=%s,h1=%s" % (p0, p1),
             "--timeline_path", out],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stderr
        with open(out) as f:
            t = json.load(f)
        pids = {ev["pid"] for ev in t["traceEvents"]}
        assert pids == {0, 1}


class TestApiSpec:
    def test_api_spec_is_current(self):
        """The committed API.spec must match the live surface (reference
        CI: tools/diff_api.py).  Regenerate with:
        python tools/print_signatures.py > API.spec"""
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import print_signatures
        finally:
            sys.path.pop(0)
        live = list(print_signatures.iter_api())
        with open(os.path.join(REPO, "API.spec")) as f:
            frozen = [l.rstrip("\n") for l in f if l.strip()]
        missing = set(frozen) - set(live)
        added = set(live) - set(frozen)
        assert not missing and not added, (
            "API surface changed; regenerate API.spec\n"
            "removed: %s\nadded: %s" % (sorted(missing)[:10],
                                        sorted(added)[:10]))
