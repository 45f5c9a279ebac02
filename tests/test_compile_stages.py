"""Compilation traced where it happens: jax's trace, lowering and backend
compile as children of the runner phase that paid for them, a cause for
every recompile, honest compile totals in the journal, and the two
benchmark readers of the set-up's stages (``chipbench/compile_spans.py``).

CPU, tiny programs.
"""

import os
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.observability as obs
from paddle_tpu.executor import Scope, scope_guard
from paddle_tpu.observability import journal as oj
from paddle_tpu.observability import tracing as tr

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from chipbench import compile_spans  # noqa: E402
from chipbench import manifest as mf  # noqa: E402
from chipbench import program_spans  # noqa: E402

STAGES = ("trace", "lower", "backend")


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    fluid.unique_name.switch()
    for var in ("PADDLE_TPU_TELEMETRY", "PADDLE_TPU_TELEMETRY_DIR",
                "PADDLE_TPU_TRACING", "PADDLE_TPU_TRACEPARENT",
                "PADDLE_TPU_TRACE_RING"):
        monkeypatch.delenv(var, raising=False)
    # nothing is kept for being sampled: what the ring holds was kept for
    # compiling (or for being slow)
    monkeypatch.setenv("PADDLE_TPU_TRACE_SAMPLE", "0")
    obs.reset_telemetry()
    yield
    obs.reset_telemetry()


def _program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.fc(x, size=4))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _batch(rows=8, seed=0):
    return {"x": np.random.RandomState(seed).rand(rows, 8).astype("float32")}


def _tree(records, root):
    """``{name: [records]}`` of every descendant of ``root``."""
    ids, out = {root["span"]}, {}
    for r in sorted(records, key=lambda r: r["t0_ns"]):
        if r["parent"] in ids:
            ids.add(r["span"])
            out.setdefault(r["name"], []).append(r)
    return out


def _steps(runner="executor"):
    recs = tr.get_tracer().records()
    return recs, {r["attrs"]["step"]: r for r in recs
                  if r["name"] == runner + ".step"}


def _end(r):
    return r["t0_ns"] + r["dur_ms"] * 1e6


def _children(records, parent):
    return [r for r in records if r["parent"] == parent["span"]]


class TestStageSpans:
    def test_first_run_is_kept_with_its_stages_under_dispatch(self):
        main, startup, loss = _program()
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            exe.run(main, feed=_batch(), fetch_list=[loss])
            exe.run(main, feed=_batch(seed=1), fetch_list=[loss])
        recs, steps = _steps()
        # sampled out (1 in 0): the two that compiled are kept, the hit not
        assert sorted(steps) == [0, 1]
        first = steps[1]
        assert first["attrs"]["compiled"] is True
        tree = _tree(recs, first)
        dispatch, = tree["executor.dispatch"]
        under = {s: [r for r in _children(recs, dispatch)
                     if r["name"] == "executor.compile." + s]
                 for s in STAGES}
        for stage in STAGES:
            assert under[stage], stage
            for r in under[stage]:
                assert r["attrs"]["fun"]
                assert dispatch["t0_ns"] <= r["t0_ns"]
                assert _end(r) <= _end(dispatch) + 1e3
        assert [r["attrs"]["fun"] for r in under["trace"]] == ["step_once"]
        backend, = under["backend"]
        assert backend["attrs"]["fun"] == "jit(step_once)"
        assert backend["attrs"]["source"] in ("compiled", "persistent_cache")
        # the cost model's registration is the compile phase's own child
        compile_, = tree["executor.compile"]
        assert compile_["attrs"]["cause"] == "first"
        telemetry, = tree["executor.compile.telemetry"]
        assert telemetry["parent"] == compile_["span"]

    def test_a_second_run_with_the_same_feed_holds_no_stage(
            self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_TRACE_SAMPLE", "1")
        obs.reset_telemetry()
        main, startup, loss = _program()
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            exe.run(main, feed=_batch(), fetch_list=[loss])
            exe.run(main, feed=_batch(seed=1), fetch_list=[loss])
        recs, steps = _steps()
        second = steps[2]
        assert "compiled" not in second["attrs"]
        assert not [n for n in _tree(recs, second) if ".compile" in n]

    def test_nested_traces_count_once(self):
        """The step's trace holds a trace of every op's function inside
        it: one span is left, as long as the outermost event and no
        longer than the phase it lies in."""
        from jax import monitoring

        raw, outermost = [], []

        def listen(event, duration, **kw):
            if event == "/jax/core/compile/jaxpr_trace_duration":
                raw.append(kw.get("fun_name"))
                if kw.get("fun_name") == "step_once":
                    outermost.append(duration * 1e3)

        main, startup, loss = _program()
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            monitoring.register_event_duration_secs_listener(listen)
            try:
                exe.run(main, feed=_batch(), fetch_list=[loss])
            finally:
                monitoring.unregister_event_duration_listener(listen)
        recs, steps = _steps()
        dispatch, = _tree(recs, steps[1])["executor.dispatch"]
        traces = [r for r in _children(recs, dispatch)
                  if r["name"] == "executor.compile.trace"]
        assert len(raw) > len(traces) == 1
        stage_ms = sum(r["dur_ms"] for r in traces)
        assert outermost[-1] - 0.05 <= stage_ms <= dispatch["dur_ms"]

    def test_nested_events_by_hand(self):
        """Inner first, then the outer that covers them; an event inside
        a kept one of its stage; a trace inside a lowering is the
        lowering's time."""
        tr.watch_compiles()
        event = tr._on_compile_event
        with tr.phase("executor.step", step=0):
            with tr.phase("executor.dispatch") as ph:
                t_start = time.perf_counter()
                time.sleep(0.004)
                event("/jax/core/compile/jaxpr_trace_duration", 0.002,
                      fun_name="inner")
                time.sleep(0.004)
                event("/jax/core/compile/jaxpr_trace_duration", 0.003,
                      fun_name="inner2")
                event("/jax/core/compile/jaxpr_trace_duration",
                      time.perf_counter() - t_start, fun_name="outer")
                t_lower = time.perf_counter()
                time.sleep(0.002)
                event("/jax/core/compile/jaxpr_trace_duration", 0.001,
                      fun_name="in_lowering")
                time.sleep(0.002)
                event("/jax/core/compile/jaxpr_to_mlir_module_duration",
                      time.perf_counter() - t_lower, fun_name="lower")
                stages = tr.compile_stages(ph)
        assert stages["trace_ms"] >= 8.0 and stages["lower_ms"] >= 4.0
        assert stages["backend_ms"] == 0.0 and stages["source"] is None
        assert stages["union_ms"] == pytest.approx(
            stages["trace_ms"] + stages["lower_ms"])
        assert stages["union_ms"] <= ph.dur_ms
        recs = tr.get_tracer().records()
        assert [r["attrs"]["fun"] for r in recs
                if ".compile." in r["name"]] == ["lower", "outer"]
        # a trace alone keeps no step: this one was kept for its lowering
        step, = [r for r in recs if r["name"] == "executor.step"]
        assert step["attrs"]["compiled"] is True

    def test_outside_any_phase_nothing_is_recorded(self):
        tr.watch_compiles()
        tr._on_compile_event("/jax/core/compile/backend_compile_duration",
                             0.5, fun_name="eager")
        with tr.phase("executor.step", step=0) as ph:
            assert tr.compile_stages(ph) is None
        # nothing kept the step: it neither compiled nor was sampled
        assert tr.get_tracer().records() == []

    def test_the_kill_switch_records_nothing(self):
        tr.set_tracing_enabled(False)
        main, startup, loss = _program()
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            exe.run(main, feed=_batch(), fetch_list=[loss])
            with tr.phase("executor.step", step=9) as ph:
                tr._on_compile_event(
                    "/jax/core/compile/backend_compile_duration", 0.0,
                    fun_name="f")
                assert tr.compile_stages(ph) is None
        assert tr.get_tracer().records() == []
        # the journal still has its compile event, with the block's build
        # alone
        ev = oj.get_journal().events("compile")[-1]
        assert ev["cause"] == "first" and ev["trace_ms"] == 0.0


class TestCause:
    def _compile_events(self):
        return oj.get_journal().events("compile")

    def test_first_feed_shape_and_program_version(self):
        main, startup, loss = _program()
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            exe.run(main, feed=_batch(8), fetch_list=[loss])
            exe.run(main, feed=_batch(8, seed=1), fetch_list=[loss])
            exe.run(main, feed=_batch(4), fetch_list=[loss])
            main._bump_version()
            exe.run(main, feed=_batch(4), fetch_list=[loss])
        events = self._compile_events()
        # the startup program, then the step's three compiles
        assert [(e["step"], e["cause"]) for e in events] == [
            (0, "first"), (1, "first"), (3, "feed_signature"),
            (4, "program_version")]
        for e in events:
            assert e["runner"] == "executor"
            assert e["trace_ms"] > 0 and e["lower_ms"] > 0 \
                and e["backend_ms"] > 0
            assert e["source"] in ("compiled", "persistent_cache")
            # the build plus the stages' union, which the three bound
            assert e["compile_ms"] >= max(
                e["trace_ms"], e["lower_ms"], e["backend_ms"])
            assert e["compile_ms"] <= e["trace_ms"] + e["lower_ms"] \
                + e["backend_ms"] + 1e3
        recs, steps = _steps()
        assert sorted(steps) == [0, 1, 3, 4]
        compile_, = _tree(recs, steps[3])["executor.compile"]
        assert compile_["attrs"]["cause"] == "feed_signature"
        misses = {dict(m.labels)["cause"]: m.value
                  for m in obs.registry().collect()
                  if m.name == "jit_cache_misses_total"}
        assert misses == {"first": 2.0, "feed_signature": 1.0,
                          "program_version": 1.0}
        hist = obs.histogram("compile_ms", runner="executor")
        assert hist.count == 4
        assert hist.sum == pytest.approx(
            sum(e["compile_ms"] for e in events), abs=0.05)

    @pytest.mark.parametrize("change,cause", [
        ("fetch", "fetch_list"), ("scope", "scope"),
        ("no_cache", "no_program_cache")])
    def test_other_causes(self, change, cause):
        main, startup, loss = _program()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = Scope()
        with scope_guard(scope):
            exe.run(startup)
            exe.run(main, feed=_batch(), fetch_list=[loss])
            if change == "fetch":
                w = main.global_block().all_parameters()[0]
                exe.run(main, feed=_batch(), fetch_list=[loss, w])
            elif change == "no_cache":
                exe.run(main, feed=_batch(), fetch_list=[loss],
                        use_program_cache=False)
        if change == "scope":
            other = Scope()
            with scope_guard(other):
                exe.run(startup)
            with scope_guard(other):
                exe.run(main, feed=_batch(), fetch_list=[loss])
        last = self._compile_events()[-1]
        assert last["cause"] == cause and last["step"] == exe._step - 1

    def test_data_parallel_second_step_compiles_on_its_arguments(self):
        """The startup program leaves the state on one device; the first
        data-parallel step compiles (``first``), and the second, a hit,
        is compiled again by jax for the state's new shardings."""
        main, startup, loss = _program()
        prog = fluid.CompiledProgram(main).with_data_parallel(
            loss_name=loss.name)
        exe = fluid.Executor(fluid.CPUPlace())
        with scope_guard(Scope()):
            exe.run(startup)
            for i in range(3):
                exe.run(prog, feed=_batch(seed=i), fetch_list=[loss])
        spmd = [e for e in self._compile_events() if e["runner"] == "spmd"]
        assert [(e["step"], e["cause"]) for e in spmd] == [
            (1, "first"), (2, "arguments")]
        assert spmd[1]["backend_ms"] > 0
        recs, steps = _steps("spmd")
        assert sorted(steps) == [1, 2]
        assert steps[2]["attrs"]["cause"] == "arguments"
        # the step's own backend stage under each step's dispatch (jax
        # compiles helpers of its own there and elsewhere, a slicing of
        # the arguments onto the devices say)
        for s in (1, 2):
            dispatch, = _tree(recs, steps[s])["spmd.dispatch"]
            assert [r["attrs"]["fun"] for r in _children(recs, dispatch)
                    if r["name"] == "spmd.compile.backend"].count(
                        "jit(traced)") == 1


# -- the benchmark's readers ---------------------------------------------------

def _span(name, t0_ms, dur_ms, span="s", parent="p", **attrs):
    return {"name": name, "span": span, "parent": parent,
            "t0_ns": int(t0_ms * 1e6), "dur_ms": dur_ms, "attrs": attrs}


def _read(metric, state):
    return mf.load_by_name("layer_metrics", metric).read({"state": state})


def test_readers_on_a_hand_made_ring(monkeypatch):
    """Window opens at 10 s.  Before it: the startup program (trace
    100-110, lower 110-130, backend 130-330 ms, with a trace nested in the
    lowering at 115-120), the step (trace 1000-4000 with a nested trace
    1500-1600 and a stray record of the same interval, lower 4000-5000,
    backend 5000-7000 from the cache) and dp4's second backend stage under
    spmd (8000-9000).  After it: an eval program's stages, left out."""
    ring = [
        _span("executor.step", 90, 400, step=0),
        _span("executor.compile.trace", 100, 10),
        _span("executor.compile.lower", 110, 20),
        _span("executor.compile.trace", 115, 5),
        _span("executor.compile.backend", 130, 200, source="compiled"),
        _span("executor.compile.telemetry", 50, 5),      # not a jax stage
        _span("executor.compile.trace", 1000, 3000),
        _span("executor.compile.trace", 1500, 100),
        _span("executor.compile.trace", 1000, 3000),
        _span("executor.compile.lower", 4000, 1000),
        _span("executor.compile.backend", 5000, 2000,
              source="persistent_cache"),
        _span("spmd.compile.backend", 8000, 1000, source="compiled"),
        _span("host.compile.trace", 9000, 500),           # not a runner
        _span("executor.compile.trace", 11000, 50),
        _span("executor.compile.lower", 11050, 50),
        _span("executor.compile.backend", 11100, 900),
    ]
    monkeypatch.setattr(program_spans, "ring_records", lambda: ring)
    state = {"t_window": 10.0, "window_s": 30.0}
    assert _read("setup_trace_lower_s", state) == pytest.approx(
        (10 + 20 + 3000 + 1000) / 1e3)
    assert _read("setup_backend_compile_s", state) == pytest.approx(
        (200 + 2000 + 1000) / 1e3)
    # a window that opens earlier leaves more out
    assert _read("setup_backend_compile_s", {"t_window": 1.0}) \
        == pytest.approx(0.2)
    assert compile_spans.stage_of(ring[5]) is None
    assert compile_spans.stage_of(ring[12]) is None
    assert compile_spans.stage_of(ring[11]) == "backend"


@pytest.mark.parametrize("ring", [
    [],
    [_span("executor.step", 0, 10), _span("executor.compile", 1, 5),
     _span("executor.dispatch", 6, 3)],
    [{"name": "executor.compile.trace", "span": "a", "parent": None,
      "ts": 1.0, "dur_ms": 4.0}],
], ids=["no_tracer", "parent_phases", "no_monotonic_start"])
def test_readers_without_stage_spans_give_none(ring, monkeypatch):
    monkeypatch.setattr(program_spans, "ring_records", lambda: ring)
    state = {"t_window": 10.0, "window_s": 30.0}
    assert _read("setup_trace_lower_s", state) is None
    assert _read("setup_backend_compile_s", state) is None


def test_readers_on_the_live_ring_of_a_run():
    """What the program's own run leaves: every stage before the window
    counted, nothing after it."""
    main, startup, loss = _program()
    exe = fluid.Executor(fluid.CPUPlace())
    with scope_guard(Scope()):
        exe.run(startup)
        exe.run(main, feed=_batch(), fetch_list=[loss])
        t_window = time.perf_counter()
        exe.run(main, feed=_batch(4), fetch_list=[loss])
    state = {"t_window": t_window, "window_s": 1.0}
    compiles = oj.get_journal().events("compile")
    trace_lower = _read("setup_trace_lower_s", state)
    backend = _read("setup_backend_compile_s", state)
    # at least the journal's stage times of the two set-up compiles (to
    # its rounding): the rng_key phase's eager compiles are set-up too
    assert trace_lower >= sum(e["trace_ms"] + e["lower_ms"]
                              for e in compiles[:2]) / 1e3 - 1e-4
    assert backend >= sum(e["backend_ms"] for e in compiles[:2]) / 1e3 \
        - 1e-4
    everything = _read("setup_backend_compile_s", {"t_window": 1e9})
    assert everything > backend
