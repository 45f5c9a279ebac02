"""Distributed tracing (ISSUE 13): span round-trip + torn-write
tolerance, the ``PADDLE_TPU_TRACING=0`` kill switch, context
propagation in-thread / cross-thread (the ``run_batches`` prefetch
worker) / cross-process (traceparent env), critical-path attribution
and the ``tools.trace`` CLI contract, and the flight recorder firing on
a dispatcher crash.  The full multi-process elastic drill (ONE trace
across victim + survivors) is the slow-marked acceptance test.
"""

import gc
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.observability as obs
from paddle_tpu import serving
from paddle_tpu.executor import Scope, scope_guard
from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
from paddle_tpu.observability import journal as oj
from paddle_tpu.observability import tracing as tr
from paddle_tpu.tools import trace as trace_cli

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    fluid.unique_name.switch()
    for var in ("PADDLE_TPU_TELEMETRY", "PADDLE_TPU_TELEMETRY_DIR",
                "PADDLE_TPU_TELEMETRY_FLUSH", "PADDLE_TPU_TRACING",
                "PADDLE_TPU_TRACEPARENT", "PADDLE_TPU_TRACE_RING"):
        monkeypatch.delenv(var, raising=False)
    obs.reset_telemetry()
    yield
    obs.reset_telemetry()


def _trace_dir(monkeypatch, tmp_path, flush=1):
    tdir = tmp_path / "telemetry"
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_DIR", str(tdir))
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY_FLUSH", str(flush))
    obs.reset_telemetry()
    return str(tdir)


# ---------------------------------------------------------------------------
# span model: ids, round-trip, torn lines, kill switch
# ---------------------------------------------------------------------------
class TestSpanModel:
    def test_round_trip_parent_child(self, tmp_path, monkeypatch):
        tdir = _trace_dir(monkeypatch, tmp_path)
        with tr.span("outer", step=3) as outer:
            with tr.span("inner") as inner:
                inner.set_attr("rows", 8)
        tr.get_tracer().flush()
        recs = tr.read_traces(tdir)
        by_name = {r["name"]: r for r in recs}
        assert set(by_name) == {"outer", "inner"}
        o, i = by_name["outer"], by_name["inner"]
        assert i["trace"] == o["trace"] == outer.trace_id
        assert i["parent"] == o["span"]
        assert o["parent"] is None
        assert i["attrs"]["rows"] == 8 and o["attrs"]["step"] == 3
        assert o["status"] == i["status"] == "ok"
        assert o["dur_ms"] >= i["dur_ms"] >= 0
        assert o["pid"] == os.getpid()

    def test_error_status_flushes_urgently(self, tmp_path, monkeypatch):
        tdir = _trace_dir(monkeypatch, tmp_path, flush=1000)
        with pytest.raises(ValueError):
            with tr.span("doomed"):
                raise ValueError("boom")
        # no explicit flush: the error terminal must already be on disk
        recs = tr.read_traces(tdir)
        assert recs and recs[0]["status"] == "error:ValueError"

    def test_torn_trailing_line_is_skipped(self, tmp_path, monkeypatch):
        tdir = _trace_dir(monkeypatch, tmp_path)
        with tr.span("kept"):
            pass
        tr.get_tracer().flush()
        path = tr.get_tracer().path
        with open(path, "a") as f:
            f.write('{"schema": 1, "kind": "span", "trunc')  # SIGKILL
        recs = tr.read_traces(tdir)
        assert [r["name"] for r in recs] == ["kept"]
        # future-schema records are skipped too, never raised
        with open(path, "a") as f:
            f.write(json.dumps({"schema": 99, "span": "x",
                                "name": "future"}) + "\n")
        assert [r["name"] for r in tr.read_traces(tdir)] == ["kept"]

    def test_kill_switch_zero_growth(self, tmp_path, monkeypatch):
        tdir = _trace_dir(monkeypatch, tmp_path)
        monkeypatch.setenv("PADDLE_TPU_TRACING", "0")
        obs.reset_telemetry()
        s = tr.span("invisible", big=1)
        assert s is tr.NULL_SPAN and not s.recording
        with s:
            assert tr.current_span() is None
            assert tr.current_traceparent() is None
        s.end("never")
        assert len(tr.get_tracer()) == 0
        tr.get_tracer().flush()
        assert not [n for n in os.listdir(tdir)
                    if n.startswith("trace-")]
        # flight dump is a no-op when killed, never a second failure
        assert tr.flight_dump("whatever") is None

    def test_traceparent_round_trip_and_tolerance(self):
        ctx = tr.new_trace_context()
        assert tr.parse_traceparent(tr.format_traceparent(ctx)) == ctx
        for bad in (None, "", "nope", "00-zz-yy-01", "00--01", 42):
            assert tr.parse_traceparent(bad) is None

    def test_ring_is_bounded(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_TRACE_RING", "4")
        obs.reset_telemetry()
        for i in range(10):
            tr.span("s%d" % i).end()
        assert len(tr.get_tracer()) == 4


# ---------------------------------------------------------------------------
# context propagation: threads and processes
# ---------------------------------------------------------------------------
class TestPropagation:
    def test_capture_use_context_across_thread(self):
        got = {}
        with tr.span("root") as root:
            ctx = tr.capture_context()

            def worker():
                with tr.use_context(ctx):
                    with tr.span("child") as c:
                        got["trace"] = c.trace_id
                        got["parent"] = c.parent_id

            t = threading.Thread(target=worker)
            t.start()
            t.join()
        assert got["trace"] == root.trace_id
        assert got["parent"] == root.span_id

    def test_remote_parent_from_env(self, monkeypatch):
        ctx = tr.new_trace_context()
        monkeypatch.setenv(tr.TRACEPARENT_ENV, tr.format_traceparent(ctx))
        obs.reset_telemetry()
        with tr.span("adopted") as s:
            assert s.trace_id == ctx.trace_id
            assert s.parent_id == ctx.span_id

    def test_run_batches_prefetch_thread_joins_trace(self, tmp_path):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            out = fluid.layers.fc(x, size=3)
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            fluid.io.save_inference_model(
                str(tmp_path / "m"), ["x"], [out], exe, main_program=main)
        pred = AnalysisPredictor(
            AnalysisConfig(model_dir=str(tmp_path / "m")))
        rng = np.random.RandomState(0)
        feeds = [{"x": rng.standard_normal((2, 4)).astype("float32")}
                 for _ in range(4)]
        with tr.span("client") as root:
            results = list(pred.run_batches(feeds, max_in_flight=2))
        assert len(results) == 4
        recs = tr.get_tracer().records()
        pf = [r for r in recs if r["name"] == "pipeline.prefetch"]
        assert pf, "prefetch thread emitted no span"
        # the prefetch worker runs on its own thread yet joins the
        # caller's trace — that's the cross-thread propagation contract
        assert pf[0]["trace"] == root.trace_id
        assert pf[0]["thread"] != root.thread
        assert pf[0]["attrs"]["items"] == 4

    def test_cross_process_env_propagation(self, tmp_path, monkeypatch):
        tdir = _trace_dir(monkeypatch, tmp_path)
        with tr.span("parent-proc") as root:
            env = dict(os.environ)
            env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
                        "PADDLE_TPU_TELEMETRY_DIR": tdir,
                        "PADDLE_TPU_TELEMETRY_FLUSH": "1"})
            tr.inject_env(env)
            assert env[tr.TRACEPARENT_ENV] == root.traceparent
            res = subprocess.run(
                [sys.executable, "-c",
                 "from paddle_tpu.observability import tracing as t\n"
                 "t.span('child-proc').end()\n"
                 "t.get_tracer().flush()"],
                capture_output=True, text=True, timeout=120, env=env,
                cwd=REPO)
        assert res.returncode == 0, res.stderr[-800:]
        tr.get_tracer().flush()
        recs = [r for r in tr.read_traces(tdir)
                if r["trace"] == root.trace_id]
        names = {r["name"] for r in recs}
        assert names == {"parent-proc", "child-proc"}
        pids = {r["pid"] for r in recs}
        assert len(pids) == 2, "expected two processes in one trace"


# ---------------------------------------------------------------------------
# critical path + the tools.trace CLI
# ---------------------------------------------------------------------------
def _synthetic_request(trace="t" * 32, base=1000.0, rank=0):
    """A serving.request tree with a known critical path:
    2ms queue + (1ms pad inside 2ms batch) + 4ms device + 2ms sync."""

    def rec(name, span, parent, ts, dur_ms, **attrs):
        r = {"schema": 1, "kind": "span", "ts": base + ts, "rank": rank,
             "pid": 1, "thread": "main", "trace": trace, "span": span,
             "parent": parent, "name": name, "dur_ms": dur_ms,
             "status": "ok"}
        if attrs:
            r["attrs"] = attrs
        return r

    return [
        rec("serving.request", "a1", None, 0.0, 10.0),
        rec("serving.queue_wait", "a2", "a1", 0.0, 2.0),
        rec("serving.batch", "a3", "a1", 0.002, 2.0),
        rec("serving.pad", "a4", "a3", 0.002, 1.0),
        rec("serving.device", "a5", "a1", 0.004, 4.0),
        rec("serving.sync", "a6", "a1", 0.008, 2.0),
    ]


class TestCriticalPath:
    def test_attribution_sums_to_root(self):
        spans = _synthetic_request()
        segments = trace_cli.critical_path(spans)
        contrib = {rec["name"]: ms for rec, ms in segments}
        assert segments[0][0]["name"] == "serving.request"
        assert contrib["serving.queue_wait"] == pytest.approx(2.0, abs=.1)
        assert contrib["serving.pad"] == pytest.approx(1.0, abs=0.1)
        assert contrib["serving.batch"] == pytest.approx(1.0, abs=0.1)
        assert contrib["serving.device"] == pytest.approx(4.0, abs=0.1)
        assert contrib["serving.sync"] == pytest.approx(2.0, abs=0.1)
        total = sum(ms for _, ms in segments)
        assert total == pytest.approx(10.0, abs=0.2)

    def test_open_spans_excluded_and_summary(self):
        spans = _synthetic_request()
        spans.append({"schema": 1, "ts": 1000.0, "trace": "t" * 32,
                      "span": "a7", "parent": "a1", "name": "hung",
                      "dur_ms": None, "status": "ok", "open": True,
                      "rank": 2})
        assert all(rec["name"] != "hung"
                   for rec, _ in trace_cli.critical_path(spans))
        info = trace_cli.trace_summary("t" * 32, spans)
        assert info["root"] == "serving.request"
        assert info["dur_ms"] == 10.0
        assert info["ranks"] == [0, 2]

    def test_serving_stats_and_alert_exit_codes(self, tmp_path, capsys):
        path = tmp_path / "trace-r0-1.jsonl"
        lines = []
        for i in range(3):
            lines.extend(json.dumps(r) for r in _synthetic_request(
                trace=("%032x" % i), base=1000.0 + i))
        path.write_text("\n".join(lines) + "\n")
        stats = trace_cli.serving_stats(
            trace_cli.group_traces(tr.read_traces(str(path))))
        assert stats["requests"] == 3
        assert stats["queue_wait_p99_ms"] == pytest.approx(2.0, abs=0.1)
        assert stats["sync_p99_ms"] == pytest.approx(2.0, abs=0.1)

        rc = trace_cli.main([str(tmp_path), "--serving", "--json"])
        assert rc == 0
        st = json.loads(capsys.readouterr().out)
        assert st["request_p99_ms"] == pytest.approx(10.0, abs=0.1)
        assert trace_cli.main(
            [str(tmp_path), "--serving", "--alert",
             "queue_wait_p99_ms>100"]) == 0
        assert trace_cli.main(
            [str(tmp_path), "--serving", "--alert",
             "queue_wait_p99_ms>1"]) == 1
        assert trace_cli.main(
            [str(tmp_path), "--serving", "--alert",
             "no_such_field>1"]) == 2
        capsys.readouterr()

    def test_id_view_and_chrome_export(self, tmp_path, capsys):
        path = tmp_path / "trace-r0-1.jsonl"
        path.write_text("\n".join(
            json.dumps(r) for r in _synthetic_request()) + "\n")
        out_json = str(tmp_path / "chrome.json")
        rc = trace_cli.main([str(tmp_path), "--id", "tttttttt",
                             "--chrome", out_json])
        assert rc == 0
        out = capsys.readouterr().out
        assert "critical path" in out and "serving.device" in out
        with open(out_json) as f:
            ct = json.load(f)
        xs = [e for e in ct["traceEvents"] if e.get("ph") == "X"]
        assert len(xs) == 6
        assert all(e["pid"] == "rank0" for e in xs)

    def test_empty_dir_exits_2(self, tmp_path, capsys):
        assert trace_cli.main([str(tmp_path)]) == 2
        capsys.readouterr()


# ---------------------------------------------------------------------------
# step phases (ISSUE 26): the step's span tree, tail sampling, a true
# step time, GC pauses
# ---------------------------------------------------------------------------
PHASES = ["fusion_resolve", "feed_stage", "lookup", "gather_state",
          "rng_key", "dispatch", "apply_results", "finish_fetches"]


def _train_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, size=4))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _batch(i=0):
    return {"x": np.random.RandomState(i).rand(8, 8).astype("float32")}


def _steps_of(records, runner):
    """Kept steps of ``runner`` as ``(step record, its descendants)``."""
    out = []
    for root in records:
        if root["name"] != runner + ".step":
            continue
        ids, kids = {root["span"]}, []
        for r in sorted(records, key=lambda r: r["t0_ns"]):
            if r["parent"] in ids:
                ids.add(r["span"])
                kids.append(r)
        out.append((root, kids))
    return out


def _end_ns(rec):
    return rec["t0_ns"] + rec["dur_ms"] * 1e6


def _collect_cycles(n=300000):
    """Force a collection that takes well over a millisecond: ``n`` lists
    in one cycle, garbage by the time ``gc.collect()`` runs."""
    junk = [[] for _ in range(n)]
    for a, b in zip(junk, junk[1:]):
        a.append(b)
    junk[-1].append(junk[0])
    del junk, a, b
    gc.collect()


class TestStepPhases:
    @pytest.mark.parametrize("runner", ["executor", "spmd"])
    def test_kept_step_tree(self, runner, monkeypatch):
        """Both runners: a kept step's tree has exactly the table's
        children under the runner's prefix, in order, none overlapping,
        each inside its parent; ``compile`` on the first step alone, with
        its ``compile.telemetry`` child, and jax's trace, lower and backend
        stages under the phases that paid for them (the step is kept as
        ``compiled``); ``host.sync`` inside ``finish_fetches`` when the
        call synced."""
        monkeypatch.setenv("PADDLE_TPU_TRACE_SAMPLE", "1")
        obs.reset_telemetry()
        main, startup, loss = _train_program()
        prog = main if runner == "executor" else \
            fluid.CompiledProgram(main).with_data_parallel(
                loss_name=loss.name)
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            exe.run(prog, feed=_batch(0), fetch_list=[loss])
            exe.run(prog, feed=_batch(1), fetch_list=[loss])
            h = exe.run(prog, feed=_batch(2), fetch_list=[loss],
                        return_numpy=False)
            recs = tr.get_tracer().records()
            float(h[0])
        first, steady, lazy = _steps_of(recs, runner)[-3:]
        with_compile = PHASES[:3] + ["compile"] + PHASES[3:]
        for (root, kids), names, synced in ((first, with_compile, True),
                                            (steady, PHASES, True),
                                            (lazy, PHASES, False)):
            # a collection may fall anywhere (the compile allocates)
            kids = [k for k in kids if k["name"] != "host.gc"]
            direct = [k for k in kids if k["parent"] == root["span"]]
            assert [k["name"] for k in direct] == [
                "%s.%s" % (runner, n) for n in names]
            assert root["attrs"]["runner"] == runner
            assert root["attrs"]["lazy"] is (not synced)
            for k in kids:
                assert k["trace"] == root["trace"]
                parent = root if k["parent"] == root["span"] else \
                    [p for p in kids if p["span"] == k["parent"]][0]
                assert parent["t0_ns"] <= k["t0_ns"]
                assert _end_ns(k) <= _end_ns(parent) + 1e3
            for a, b in zip(direct, direct[1:]):
                assert _end_ns(a) <= b["t0_ns"] + 1e3, (a, b)
            inner = [k for k in kids if k["parent"] != root["span"]]
            stages = [k for k in inner if ".compile." in k["name"]]
            inner = [k for k in inner if k not in stages]
            assert [k["name"] for k in inner] == (
                ["host.sync"] if synced else [])
            if synced:
                assert inner[0]["parent"] == direct[-1]["span"]
            if names is PHASES and not (runner == "spmd"
                                        and root is steady[0]):
                assert not stages and "compiled" not in root["attrs"]
            elif names is PHASES:
                # the lookup hit, and jax compiled the step again: the
                # startup program left the state off the mesh's shardings
                assert root["attrs"]["cause"] == "arguments"
                assert "spmd.compile.backend" in {k["name"] for k in stages}
            else:
                assert root["attrs"]["compiled"] is True
                assert {k["name"] for k in stages} >= {
                    "%s.compile.%s" % (runner, s)
                    for s in ("telemetry", "trace", "lower", "backend")}
            stage = direct[1]["attrs"]
            assert stage["bytes"] == 8 * 8 * 4 and stage["misses"] == 1
            assert direct[2]["attrs"]["hit"] is (names is PHASES)
            assert direct[-1]["attrs"]["handles"] == (0 if synced else 1)
        compile_, = [k for k in first[1]
                     if k["name"] == runner + ".compile"]
        assert compile_["attrs"]["compile_ms"] > 0
        # the tool's critical path takes the children as they are
        root, kids = steady
        kids = [k for k in kids if k["name"] != "host.gc"]
        segments = trace_cli.critical_path([root] + kids)
        assert {rec["name"] for rec, _ in segments} >= {
            "%s.%s" % (runner, n) for n in PHASES}
        assert sum(ms for _, ms in segments) == pytest.approx(
            root["dur_ms"], rel=0.05)

    def test_slow_step_is_kept_though_sampled_out(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_TRACE_SAMPLE", "0")
        obs.reset_telemetry()
        main, startup, loss = _train_program()
        exe = fluid.Executor(fluid.CPUPlace())
        from paddle_tpu import pipeline

        stage = pipeline._stage

        def slow_stage(*args, **kwargs):
            time.sleep(0.25)
            return stage(*args, **kwargs)

        with scope_guard(Scope()):
            exe.run(startup)
            for i in range(12):
                exe.run(main, feed=_batch(i), fetch_list=[loss])
            # sampled out: whatever the ring holds so far was kept for
            # compiling (the startup program and the first step) or for
            # being slow (a busy CPU can make a plain step three times
            # the median)
            assert all(s["attrs"].get("slow") or s["attrs"].get("compiled")
                       for s, _ in _steps_of(tr.get_tracer().records(),
                                             "executor"))
            monkeypatch.setattr(pipeline, "_stage", slow_stage)
            exe.run(main, feed=_batch(13), fetch_list=[loss])
        (root, kids), = [s for s in _steps_of(tr.get_tracer().records(),
                                              "executor")
                         if s[0]["dur_ms"] >= 250
                         and not s[0]["attrs"].get("compiled")]
        assert root["attrs"]["slow"] is True and root["status"] == "ok"
        longest = max(kids, key=lambda k: k["dur_ms"])
        assert longest["name"] == "executor.feed_stage"
        assert longest["dur_ms"] >= 250

    def test_slow_host_sync_outside_a_step_is_kept(self, monkeypatch):
        main, startup, loss = _train_program()
        exe = fluid.Executor(fluid.CPUPlace())
        from paddle_tpu import pipeline

        copy_all = pipeline._copy_all

        def slow_copy(vals):
            time.sleep(0.25)
            return copy_all(vals)

        with scope_guard(Scope()):
            exe.run(startup)
            for i in range(12):
                float(exe.run(main, feed=_batch(i), fetch_list=[loss],
                              return_numpy=False)[0])
            monkeypatch.setattr(pipeline, "_copy_all", slow_copy)
            h = exe.run(main, feed=_batch(12), fetch_list=[loss],
                        return_numpy=False)[0]
            float(h)
        slow = [r for r in tr.get_tracer().records()
                if r["name"] == "host.sync" and r["dur_ms"] >= 250]
        assert len(slow) == 1 and slow[0]["parent"] is None
        assert slow[0]["attrs"]["slow"] is True
        assert slow[0]["attrs"]["step"] == h.step_info[1] == 13
        assert slow[0]["dur_ms"] >= 250

    def test_lazy_step_time_is_the_interval_not_the_enqueue(self):
        main, startup, loss = _train_program()
        exe = fluid.Executor(fluid.CPUPlace())

        def count(name):
            return obs.histogram(name, runner="executor").count

        with scope_guard(Scope()):
            exe.run(startup)
            exe.run(main, feed=_batch(0), fetch_list=[loss])
            wall0, enq0 = count("step_wall_ms"), count("step_enqueue_ms")
            handles = [exe.run(main, feed=_batch(i), fetch_list=[loss],
                               return_numpy=False)[0] for i in range(4)]
            # four steps enqueued, none read: no step time was observed
            assert count("step_enqueue_ms") == enq0 + 4
            assert count("step_wall_ms") == wall0
            assert count("step_interval_ms") == 0
            assert obs.runtime.last_step_info()["step"] == 1
            float(handles[1])       # two steps after the synced one
            assert count("step_interval_ms") == 1
            assert count("step_latency_ms") == 1
            assert count("step_wall_ms") == wall0 + 1
            assert obs.runtime.last_step_info()["step"] == 3
            float(handles[0])       # an older step: nothing new is learnt
            assert count("step_interval_ms") == 1
            time.sleep(0.05)
            float(handles[3])
            assert count("step_interval_ms") == 2
            # the interval is per step advanced: 50 ms and more, over two
            # steps
            h = obs.histogram("step_interval_ms", runner="executor")
            assert h.to_dict()["max"] >= 25
            assert obs.runtime.last_step_info()["step_ms"] >= 25

    def test_step_intervals_are_per_executor(self):
        """Step numbers are each executor's own: a train and an eval
        executor whose lazy handles interleave each get the interval
        between their own completions."""
        main, startup, loss = _train_program()
        slow_exe = fluid.Executor(fluid.CPUPlace())
        fast_exe = fluid.Executor(fluid.CPUPlace())
        h = obs.histogram("step_interval_ms", runner="executor")
        with scope_guard(Scope()):
            slow_exe.run(startup)
            for _ in range(5):      # fast_exe's counter runs ahead
                fast_exe.run(main, feed=_batch(0), fetch_list=[loss])
            for i in range(3):
                a = slow_exe.run(main, feed=_batch(i), fetch_list=[loss],
                                 return_numpy=False)[0]
                b = fast_exe.run(main, feed=_batch(i), fetch_list=[loss],
                                 return_numpy=False)[0]
                assert a.step_info[1] < b.step_info[1]
                time.sleep(0.03)
                float(b)
                float(a)    # a lower number than b's: still a completion
            # three each: the synced runs before (the startup program, the
            # five steps) were each executor's first completions
            assert h.count == 6
            assert slow_exe._last_done[0] == 3
            assert fast_exe._last_done[0] == 7
            # each over its own 30 ms sleep, none divided by the other's
            # step numbers or measured from the other's completion
            assert h.to_dict()["min"] >= 25

    def test_gc_pause_is_a_span_and_an_observation(self):
        import gc

        tr.get_tracer()                 # the hook comes with the tracer
        junk = []
        for _ in range(300000):
            a, b = [], []
            a.append(b)
            b.append(a)
            junk.append(a)
        del junk
        gc.collect()
        spans = [r for r in tr.get_tracer().records()
                 if r["name"] == "host.gc"]
        assert spans, "no host.gc span for a long collection"
        # building the graph sets off collections of its own, which
        # find little; the forced one finds the graph
        forced = max(spans, key=lambda r: r["attrs"]["collected"])
        assert forced["dur_ms"] >= 1.0
        assert forced["attrs"]["generation"] == 2
        assert forced["attrs"]["collected"] >= 500000
        assert obs.histogram("gc_pause_ms", generation="2").count >= 1
        assert obs.counter("gc_collections_total",
                           generation="2").value >= 1
        obs.reset_telemetry()
        assert tr._on_gc not in gc.callbacks

    @pytest.mark.parametrize("sample", ["1", "0"])
    def test_gc_pause_inside_a_step(self, sample, monkeypatch):
        """In a kept step the pause is a child of the phase it
        interrupted; a pause does not keep a step, and in a dropped one
        it is a span of its own.  Observed once either way."""
        monkeypatch.setenv("PADDLE_TPU_TRACE_SAMPLE", sample)
        obs.reset_telemetry()
        main, startup, loss = _train_program()
        exe = fluid.Executor(fluid.CPUPlace())
        from paddle_tpu import pipeline

        stage = pipeline._stage

        def collecting_stage(*args, **kwargs):
            _collect_cycles()
            return stage(*args, **kwargs)

        with scope_guard(Scope()):
            exe.run(startup)
            exe.run(main, feed=_batch(0), fetch_list=[loss])
            monkeypatch.setattr(pipeline, "_stage", collecting_stage)
            exe.run(main, feed=_batch(1), fetch_list=[loss])
        records = tr.get_tracer().records()
        pauses = [r for r in records if r["name"] == "host.gc"
                  and r["attrs"]["collected"] >= 300000]
        assert len(pauses) == 1 and pauses[0]["dur_ms"] >= 1.0
        # building the graph sets off collections of its own: each pause
        # is one span and one observation
        assert obs.histogram("gc_pause_ms", generation="2").count == len(
            [r for r in records if r["name"] == "host.gc"
             and r["attrs"]["generation"] == 2])
        steps = _steps_of(records, "executor")
        if sample == "0":
            # the startup program and the first step compiled: kept
            assert [s["attrs"]["step"] for s, _ in steps] == [0, 1]
            assert all(s["attrs"]["compiled"] for s, _ in steps)
            assert pauses[0]["parent"] is None
            return
        stage_span, = [k for k in steps[-1][1]
                       if k["name"] == "executor.feed_stage"]
        assert pauses[0]["parent"] == stage_span["span"]
        assert stage_span["t0_ns"] <= pauses[0]["t0_ns"] \
            and _end_ns(pauses[0]) <= _end_ns(stage_span)

    @pytest.mark.parametrize("where", ["tracer_lock", "flush_locked",
                                       "id_lock", "registry_lock"])
    def test_gc_inside_a_critical_section_takes_no_lock(
            self, where, tmp_path, monkeypatch):
        """The hook runs wherever a collection fires: inside the tracer's
        lock (and its flush), the span-id lock and the metrics registry's
        lock.  None of them is reentrant, so it may take none; what it
        noted is recorded after, outside them."""
        from paddle_tpu.observability import metrics as om

        _trace_dir(monkeypatch, tmp_path, flush=1)
        tracer = tr.get_tracer()

        def inside():
            if where == "flush_locked":
                dumps = tr.json.dumps

                def collecting_dumps(*args, **kwargs):
                    _collect_cycles()
                    return dumps(*args, **kwargs)

                monkeypatch.setattr(tr.json, "dumps", collecting_dumps)
                with tr.span("flushed"):    # ends under the lock, flushes
                    pass
                monkeypatch.setattr(tr.json, "dumps", dumps)
                return
            lock = {"tracer_lock": tracer._lock, "id_lock": tr._id_lock,
                    "registry_lock": om.registry()._lock}[where]
            with lock:
                _collect_cycles()

        t = threading.Thread(target=inside, daemon=True)
        t.start()
        t.join(60)
        assert not t.is_alive(), "deadlock: the GC hook took a lock"
        pauses = [r for r in tracer.records() if r["name"] == "host.gc"]
        assert pauses and max(p["dur_ms"] for p in pauses) >= 1.0
        assert all(p["parent"] is None for p in pauses)
        assert obs.histogram("gc_pause_ms", generation="2").count >= 1

    def test_gc_hook_with_collections_all_the_time(
            self, tmp_path, monkeypatch):
        """Four threads of steps, spans, counters and ring reads, writing
        to a telemetry directory, while every few allocations set off a
        collection and each collection counts as a pause: the hook fires
        inside every critical section there is.  (The version that built
        its span inside the hook hangs here within a second.)"""
        tdir = _trace_dir(monkeypatch, tmp_path, flush=1)
        monkeypatch.setattr(tr, "GC_SPAN_NS", 0)
        tracer = tr.get_tracer()
        stop = time.time() + 1.5

        def work(i):
            n = 0
            while time.time() < stop:
                with tr.phase("executor.step", step=n, head_sample=True):
                    with tr.phase("executor.feed_stage"):
                        junk = [[] for _ in range(20)]
                        for a in junk:
                            a.append(junk)
                with tr.span("request", i=i):
                    pass
                obs.counter("stress_total", k=str(n % 50)).inc()
                tracer.records()
                n += 1

        threads = [threading.Thread(target=work, args=(i,), daemon=True)
                   for i in range(4)]
        threshold = gc.get_threshold()
        gc.set_threshold(5, 2, 2)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
        finally:
            gc.set_threshold(*threshold)
        assert not any(t.is_alive() for t in threads), "deadlock"
        tracer.flush()
        names = {r["name"] for r in tr.read_traces(tdir)}
        assert {"host.gc", "executor.step", "executor.feed_stage",
                "request"} <= names
        seen = sum(obs.counter("gc_collections_total",
                               generation=str(g)).value for g in range(3))
        pauses = sum(obs.histogram("gc_pause_ms",
                                   generation=str(g)).count
                     for g in range(3))
        assert seen >= pauses > 100

    def test_tracing_off_allocates_no_span(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_TRACE_SAMPLE", "1")
        obs.reset_telemetry()
        tr.set_tracing_enabled(False)
        made = []
        init = tr.Span.__init__

        def counting(self, *args, **kwargs):
            made.append(args[0])
            init(self, *args, **kwargs)

        monkeypatch.setattr(tr.Span, "__init__", counting)
        main, startup, loss = _train_program()
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            for i in range(3):
                exe.run(main, feed=_batch(i), fetch_list=[loss])
                float(exe.run(main, feed=_batch(i), fetch_list=[loss],
                              return_numpy=False)[0])
        assert made == [] and len(tr.get_tracer()) == 0
        # the step's own clock does not hang on the tracer
        assert obs.histogram("step_enqueue_ms",
                             runner="executor").count == 7

    def test_flight_record_shows_the_open_phase(self, monkeypatch):
        main, startup, loss = _train_program()
        exe = fluid.Executor(fluid.CPUPlace())
        from paddle_tpu import pipeline

        stage, seen = pipeline._stage, []

        def peeking_stage(*args, **kwargs):
            seen.extend(r["name"] for r in tr.get_tracer().open_spans())
            return stage(*args, **kwargs)

        monkeypatch.setattr(pipeline, "_stage", peeking_stage)
        with scope_guard(Scope()):
            exe.run(startup)
            exe.run(main, feed=_batch(0), fetch_list=[loss])
        assert seen == ["executor.step", "executor.feed_stage"]
        assert tr.get_tracer().open_spans() == []


# ---------------------------------------------------------------------------
# flight recorder: dispatcher crash postmortem
# ---------------------------------------------------------------------------
class TestFlightRecorder:
    def _save_model(self, dirname):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4], dtype="float32")
            out = fluid.layers.fc(x, size=3)
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            fluid.io.save_inference_model(str(dirname), ["x"], [out],
                                          exe, main_program=main)
        return str(dirname)

    def test_dispatcher_crash_dumps_flight_record(
            self, tmp_path, monkeypatch):
        tdir = _trace_dir(monkeypatch, tmp_path)
        pred = AnalysisPredictor(
            AnalysisConfig(model_dir=self._save_model(tmp_path / "m")))
        server = serving.PredictorServer({"t": pred}, buckets=(2,),
                                         auto_start=False)

        def boom():
            raise RuntimeError("scheduler bug")

        monkeypatch.setattr(server, "_pick_batch_locked", boom)
        rng = np.random.RandomState(7)
        feed = {"x": rng.standard_normal((1, 4)).astype("float32")}
        r1 = server.submit("t", feed)
        server.submit("t", feed)
        server.start()
        with pytest.raises(serving.DispatcherCrashedError):
            r1.result(timeout=60)
        server.close()

        flights = tr.read_flight_records(tdir)
        assert flights, "dispatcher crash produced no flight record"
        rec = flights[0]
        assert "dispatcher-died" in rec["reason"]
        assert "scheduler bug" in rec["reason"]
        # the postmortem shows what was in flight WHEN it died: the
        # stranded request spans are captured still open
        open_names = {s["name"] for s in rec["open_spans"]}
        assert "serving.request" in open_names
        # satellite 3: the urgent journal kind carries the trace id so
        # `tools.trace --id` reconstructs the incident chain
        died = [e for e in oj.read_journal(tdir)
                if e["kind"] == "dispatcher-died"]
        assert died and died[0].get("trace") == r1.span.trace_id

    def test_flights_cli_view(self, tmp_path, monkeypatch, capsys):
        tdir = _trace_dir(monkeypatch, tmp_path)
        with tr.span("stuck"):
            tr.flight_dump("synthetic hang")
        rc = trace_cli.main([tdir, "--flights"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "synthetic hang" in out and "OPEN stuck" in out


# ---------------------------------------------------------------------------
# the acceptance drill: ONE trace across victim + survivors (slow)
# ---------------------------------------------------------------------------
@pytest.mark.slow
class TestElasticDrillTrace:
    def test_elastic_drill_is_one_trace(self, tmp_path):
        tdir = str(tmp_path / "telemetry")
        env = dict(os.environ)
        env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO})
        for var in ("PADDLE_TPU_FAULT_SPEC", "PADDLE_TPU_TELEMETRY",
                    "PADDLE_TPU_TRACING", "PADDLE_TPU_TRACEPARENT"):
            env.pop(var, None)
        res = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.tools.chaos", "--elastic",
             "--steps", "8", "--ckpt-dir", str(tmp_path / "ckpt"),
             "--telemetry-dir", tdir],
            capture_output=True, text=True, timeout=600, env=env,
            cwd=REPO)
        assert res.returncode == 0, res.stdout[-3000:] + res.stderr[-800:]
        assert "chaos[elastic]: PASS" in res.stdout
        assert "ONE trace" in res.stdout

        out = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.tools.trace",
             "--elastic", tdir, "--json"],
            capture_output=True, text=True, timeout=120, env=env,
            cwd=REPO)
        assert out.returncode == 0, out.stderr[-800:]
        st = json.loads(out.stdout)
        # every rank — victim AND survivors — contributed to the trace
        assert st["ranks"] == [0, 1, 2]
        human = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.tools.trace",
             "--elastic", tdir],
            capture_output=True, text=True, timeout=120, env=env,
            cwd=REPO)
        assert "replan" in human.stdout and "reshard" in human.stdout
