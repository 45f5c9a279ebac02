"""The readers of the program's own spans, checked on the CPU:
``python -m pytest chipbench/selftest``.

The recordings under ``testdata/`` are from the traced runs of PR 26 (see
each file's ``recorded``): the program's ring as the measured window left
it, and the traced window's xplane cut to the program's annotations and the
device's module executions.  Every expected value here was counted by hand
from those files.
"""

import json
import os
import statistics
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

from chipbench import manifest as mf  # noqa: E402
from chipbench import program_spans as ps  # noqa: E402

NEW_METRICS = ("feed_stage_ms", "rng_key_ms", "jit_call_ms", "scope_io_ms",
               "run_unattributed_ms", "dispatch_lead_ms", "gc_pause_ms_max")


def _recorded(name):
    with open(os.path.join(BENCH, "testdata", name)) as f:
        return json.load(f)


def _read(metric, state):
    return mf.load_by_name("layer_metrics", metric).read({"state": state})


# -- hand-made ---------------------------------------------------------------

def _span(name, span, parent, t0_ns, dur_ms, **attrs):
    return {"name": name, "span": span, "parent": parent, "t0_ns": t0_ns,
            "dur_ms": dur_ms, "attrs": attrs}


def _hand_made_ring(runner):
    """Two kept steps inside the window [1 s, 3 s) and one before it.  Step
    A (10 ms): feed 1, lookup 0.5, gather 0.25, rng 3, dispatch 4 with a 2 ms
    ``host.gc`` inside it, apply 0.25: children cover 9 ms.  Step B (20 ms):
    feed 3, gather 0.5, rng 5, dispatch 8, apply 0.5, and a 1.5 ms
    ``host.gc`` in its self time: children cover 18.5 ms."""
    p = runner + "."
    ms = 1000000
    return [
        _span(p + "step", "z0", None, 500 * ms, 50.0, step=1),
        _span(p + "feed_stage", "z1", "z0", 501 * ms, 40.0),
        _span(p + "step", "a0", None, 1000 * ms, 10.0, step=16),
        _span(p + "feed_stage", "a1", "a0", 1000 * ms, 1.0),
        _span(p + "lookup", "a2", "a0", 1001 * ms, 0.5),
        _span(p + "gather_state", "a3", "a0", 1002 * ms, 0.25),
        _span(p + "rng_key", "a4", "a0", 1002 * ms + 500000, 3.0),
        _span(p + "dispatch", "a5", "a0", 1005 * ms + 500000, 4.0),
        _span("host.gc", "a6", "a5", 1007 * ms, 2.0, generation=2),
        _span(p + "apply_results", "a7", "a0", 1010 * ms - 250000, 0.25),
        _span(p + "step", "b0", None, 2000 * ms, 20.0, step=32, slow=True),
        _span(p + "feed_stage", "b1", "b0", 2000 * ms, 3.0),
        _span(p + "gather_state", "b2", "b0", 2003 * ms, 0.5),
        _span(p + "rng_key", "b3", "b0", 2004 * ms, 5.0),
        _span("host.gc", "b4", "b0", 2009 * ms, 1.5, generation=2),
        _span(p + "dispatch", "b5", "b0", 2011 * ms, 8.0),
        _span(p + "apply_results", "b6", "b0", 2019 * ms, 0.5),
        _span("host.gc", "g1", None, 2500 * ms, 7.0, generation=2),
        _span("host.gc", "g2", None, 3500 * ms, 90.0, generation=2),
        _span("host.sync", "s1", None, 2600 * ms, 95.0, step=33),
    ]


@pytest.mark.parametrize("runner", ps.RUNNERS)
def test_ring_readers_by_hand(runner, monkeypatch):
    monkeypatch.setattr(ps, "ring_records", lambda: _hand_made_ring(runner))
    state = {"t_window": 1.0, "window_s": 2.0}
    # medians over the two steps in the window; the step at 0.5 s is out
    assert _read("feed_stage_ms", state) == pytest.approx((1.0 + 3.0) / 2)
    assert _read("rng_key_ms", state) == pytest.approx((3.0 + 5.0) / 2)
    assert _read("jit_call_ms", state) == pytest.approx((4.0 + 8.0) / 2)
    assert _read("scope_io_ms", state) == pytest.approx((0.5 + 1.0) / 2)
    # self time: A 10 - 9 = 1; B 20 - 18.5 = 1.5 (its host.gc is a child
    # like any other; A's lies inside dispatch and is not counted twice)
    assert _read("run_unattributed_ms", state) == pytest.approx(1.25)
    # the longest pause that STARTS in the window: 7 ms, not the 90 ms
    # one at 3.5 s
    assert _read("gc_pause_ms_max", state) == 7.0
    assert _read("gc_pause_ms_max", {"t_window": 1.2, "window_s": 0.5}) == 0.0
    assert ps.phase_ms(state, "compile") is None      # in no kept step


def test_a_program_without_the_spans_gives_nothing_to_read(monkeypatch):
    """The parent of PR 26: spans with no ``t0_ns``, no phases, no
    ``host.gc``; and a program with no tracer at all."""
    old = [{"name": "executor.step", "span": "a", "parent": None,
            "ts": 1.0, "dur_ms": 9.0},
           {"name": "executor.dispatch", "span": "b", "parent": "a",
            "ts": 1.0, "dur_ms": 8.0}]
    state = {"t_window": 0.0, "window_s": 10.0}
    for records in (old, []):
        monkeypatch.setattr(ps, "ring_records", lambda r=records: r)
        for metric in NEW_METRICS:
            if metric != "dispatch_lead_ms":
                assert _read(metric, state) is None
    assert ps.dispatch_lead_ms({"devices": {}, "program": []}) is None


def test_dispatch_lead_by_hand():
    """Two devices, three steps.  Dispatch n ends at 100n + 10; device 0
    starts step n at 100n + 90, device 1 at 100n + 92: leads 80 and 82."""
    program = [["spmd.dispatch", 100 * n + 2, 8, n] for n in (7, 8, 9)]
    program += [["spmd.rng_key", 100 * n, 2, n] for n in (7, 8, 9)]
    program += [["host.sync", 100 * n + 20, 70, n - 1] for n in (7, 8, 9)]

    def dev(skew):
        return {"modules": [["jit_step(1)", 100 * n + 90 + skew, 95]
                            for n in (7, 8, 9)]
                + [["jit_fold_in(2)", 100 * n + 5, 1] for n in (7, 8, 9)]}

    trace = {"devices": {"/device:TPU:0": dev(0), "/device:TPU:1": dev(2)},
             "program": program}
    assert sorted(ps.dispatch_leads_ns(trace)) == [80, 80, 80, 82, 82, 82]
    assert ps.dispatch_lead_ms(trace) == pytest.approx(81e-6)


def _three_steps():
    return {"devices": {"d": {"modules": [
        ["jit_step(1)", 100 * n + 90, 95] for n in (7, 8, 9)]}},
        "program": [["executor.dispatch", 100 * n + 2, 8, n]
                    for n in (7, 8, 9)]}


@pytest.mark.parametrize("cut, leads", [
    # an execution handed over before the trace opened
    (lambda t: t["devices"]["d"]["modules"].insert(0, ["jit_step(1)", 1, 95]),
     [80] * 3),
    # the same, begun 12 ns before the first dispatch did, inside the
    # allowance (95 / 4): the counts make it the stranger, not the clock
    (lambda t: t["devices"]["d"]["modules"].insert(
        0, ["jit_step(1)", 690, 95]), [80] * 3),
    # the trace opened after step 7 was dispatched and before it ran
    (lambda t: t["program"].pop(0), [80] * 2),
    # the trace closed before step 9 ran
    (lambda t: t["devices"]["d"]["modules"].pop(), [80] * 2),
    # both ends cut at once, as many executions as steps: the stranger
    # began a whole step before the first dispatch did, the last
    # dispatch after the last execution
    (lambda t: (t["devices"]["d"]["modules"].pop(),
                t["devices"]["d"]["modules"].insert(
                    0, ["jit_step(1)", 602, 95])), [80] * 2),
    # an execution whose stamp lies 12 ns before its own dispatch began
    # (PR 28's and PR 34's refusals: the planes' skew): it pairs, and
    # the lead is reported as it reads
    (lambda t: t["devices"]["d"]["modules"][1].__setitem__(1, 790),
     [80, -20, 80]),
])
def test_dispatch_lead_drops_the_ends_without_a_partner(cut, leads):
    trace = _three_steps()
    cut(trace)
    assert ps.dispatch_leads_ns(trace) == leads


MS = 1000000


def _idle_start(early_ns, steps=(40, 41, 42, 43)):
    """A traced window as the loop makes one: a step of 100 ms; the first
    dispatch begins at 5 ms on an idle device and lasts 4 ms; the device's
    stamp for the first execution lies ``early_ns`` BEFORE that dispatch
    begins (lag less skew, PERF.md 7); the later executions follow one
    another, and each later dispatch begins 6 ms after the execution
    before its own did."""
    first = 5 * MS - early_ns
    runs = [first + 100 * MS * k for k in range(len(steps))]
    starts = [5 * MS] + [r + 6 * MS for r in runs[:-1]]
    return {"devices": {"d": {"modules": [
        ["jit_step(1)", r, 99 * MS] for r in runs]}},
        "program": [["executor.dispatch", d, 4 * MS, n]
                    for d, n in zip(starts, steps)]}


@pytest.mark.parametrize("early_ns", [1, 400000, 1900000, 2 * MS, -2 * MS])
def test_dispatch_lead_pairs_a_first_execution_stamped_before_its_dispatch(
        early_ns):
    """The reader that refused PRs 28 and 34 dropped this execution, faced
    three with four steps and read nothing."""
    trace = _idle_start(early_ns)
    first = -early_ns - 4 * MS
    assert ps.dispatch_leads_ns(trace) == [first] + [90 * MS] * 3
    assert ps.dispatch_lead_ms(trace) == 90.0      # the median of four
    # and with the window's other end cut as well
    trace["devices"]["d"]["modules"].pop()
    assert ps.dispatch_leads_ns(trace) == [first] + [90 * MS] * 2


@pytest.mark.parametrize("name, shift_ms", [
    (name, shift) for name in ("program_spans_seq128.json",
                               "program_spans_dp4.json")
    for shift in (-3, -2, -1, 1, 3)])
def test_dispatch_lead_on_the_recordings_with_the_planes_skewed(name,
                                                                shift_ms):
    """Every device stamp moved against the host plane's: a number at
    every skew, the unshifted reading plus the shift."""
    rec = _recorded(name)
    straight = ps.dispatch_leads_ns(rec)
    for dev in rec["devices"].values():
        for module in dev["modules"]:
            module[1] += shift_ms * MS
    assert ps.dispatch_leads_ns(rec) == [v + shift_ms * MS
                                         for v in straight]
    assert ps.dispatch_lead_ms(rec) == pytest.approx(
        statistics.median(straight) / MS + shift_ms)


@pytest.mark.parametrize("shift_ms", [0, -1, -2, -3, 1])
def test_dispatch_lead_on_a_whole_recorded_window_from_an_idle_device(
        shift_ms):
    """The kanana cell's traced window as a run makes it (PR 35): ten
    dispatches, ten executions, the first on an idle device 624,244 ns
    after its dispatch begins.  As recorded both readers pair it; with
    the device plane 1 ms earlier (the planes' skew was measured at
    0.4-1.9 ms) that execution begins before its dispatch does, and the
    reader of PRs 26-34 dropped it, faced nine with ten steps and read
    nothing, at every shift below 0 here."""
    rec = _recorded("program_spans_kanana_window.json")
    for module in rec["devices"]["/device:TPU:0"]["modules"]:
        module[1] += shift_ms * MS
    dispatch = min((e for e in rec["program"]
                    if e[0] == "executor.dispatch"), key=lambda e: e[3])
    first = ps.step_executions(rec["devices"]["/device:TPU:0"])[0][0]
    assert (first < dispatch[1]) == (shift_ms < 0)
    leads = ps.dispatch_leads_ns(rec)
    assert len(leads) == 10
    assert leads[0] == -3000865 + shift_ms * MS
    assert ps.dispatch_lead_ms(rec) == pytest.approx(
        585.9092765 + shift_ms)         # the run's own result line, 0


@pytest.mark.parametrize("breach, reason", [
    # a dispatch in the middle whose annotation the trace lost
    (lambda t: t["program"].pop(1), "steps 7..9, 2 of them"),
    # an execution in the middle that the device plane lost
    (lambda t: t["devices"]["d"]["modules"].pop(1),
     "d: 2 executions of the step module for 3 steps"),
    # annotations without a step cannot be joined
    (lambda t: t["program"][0].__setitem__(3, None), "without a step"),
    # a step dispatched twice
    (lambda t: t["program"].append(list(t["program"][0])),
     "twice for step 7"),
    # an execution that begins 42 ns before its own dispatch did, over
    # the allowance of 95 / 4: no skew, an alignment off
    (lambda t: (t["devices"]["d"]["modules"][1].__setitem__(1, 760),
                t["devices"]["d"]["modules"][2].__setitem__(1, 860)),
     "before its dispatch began"),
    # an execution more than steps, and it is not a stranger in front
    (lambda t: t["devices"]["d"]["modules"].append(["jit_step(1)", 1090,
                                                    95]),
     "did not begin before the first dispatch"),
    (lambda t: t["devices"]["d"]["modules"].clear(),
     "no execution of the step module"),
    (lambda t: t["devices"].clear(), "no device plane"),
    (lambda t: t["program"].clear(), "no *.dispatch annotation"),
])
def test_dispatch_lead_is_none_where_it_cannot_pair(breach, reason, capsys):
    trace = _three_steps()
    assert ps.dispatch_lead_ms(trace) == pytest.approx(80e-6)
    breach(trace)
    why = []
    assert ps.dispatch_lead_ms(trace, why) is None
    assert len(why) == 1 and reason in why[0]
    # the reader says so on a line of the run's output
    from chipbench.layer_metrics import dispatch_lead_ms as reader
    orig, ps.traced_annotations = ps.traced_annotations, lambda: trace
    try:
        assert reader.read({}) is None
    finally:
        ps.traced_annotations = orig
    said = json.loads(capsys.readouterr().out.strip())
    assert said == {"dispatch_lead_ms": None, "why": why}


# -- the recordings ----------------------------------------------------------

def test_ring_readers_on_the_recorded_seq128_run(monkeypatch):
    """The window [1 s, 31.003 s) holds the kept steps 16, 32 and 48; the
    step that compiled (67.8 s, before it) and step 320 (after it) are cut
    off.  By hand from the file, per step 16 / 32 / 48:
    feed_stage 1.4448 / 1.7686 / 1.3126; rng_key 4.0762 / 3.1637 / 3.0815;
    dispatch 3.3797 / 3.4099 / 3.7396; gather_state + apply_results
    0.2992 + 0.9428 / 0.2309 + 1.0605 / 0.1832 + 0.9875; the step less its
    eight children 11.5201 - 11.0461 / 11.0003 - 10.6767 / 10.4816 -
    10.1081."""
    rec = _recorded("program_spans_seq128.json")
    monkeypatch.setattr(ps, "ring_records", lambda: rec["ring"])
    state = {"t_window": rec["t_window"], "window_s": rec["window_s"]}
    kept = ps.kept_steps(ps.in_window(rec["ring"], state))
    assert [s["attrs"]["step"] for s, _ in kept] == [16, 32, 48]
    assert _read("feed_stage_ms", state) == 1.4448
    assert _read("rng_key_ms", state) == 3.1637
    assert _read("jit_call_ms", state) == 3.4099
    assert _read("scope_io_ms", state) == pytest.approx(1.2420)
    assert _read("run_unattributed_ms", state) == pytest.approx(
        0.3735, abs=1e-3)
    # no collection of a millisecond in the window; the set-up's (inside
    # the compiling step's dispatch) and the check's lie outside it
    assert _read("gc_pause_ms_max", state) == 0.0
    assert _read("gc_pause_ms_max",
                 {"t_window": 1.0, "window_s": 60.0}) == 157.8211
    # the compiling step shows where XLA's compile lands: in `dispatch`
    (compiling, phases), = ps.kept_steps(
        [r for r in rec["ring"] if r["t0_ns"] < 0])
    assert compiling["attrs"]["step"] == 1
    assert phases["compile"][0]["dur_ms"] == 17.7744
    assert phases["dispatch"][0]["dur_ms"] == 67703.964
    assert len(phases["host.gc"]) == 1      # one more, between phases


def test_every_phase_is_on_the_recorded_host_plane_with_its_step():
    rec = _recorded("program_spans_seq128.json")
    names = {"executor." + n for n in (
        "step", "fusion_resolve", "feed_stage", "lookup", "gather_state",
        "rng_key", "dispatch", "apply_results", "finish_fetches")}
    for step in (312, 313, 314, 315):
        mine = [e for e in rec["program"] if e[3] == step]
        assert sorted(e[0] for e in mine) == sorted(names | {"host.sync"})
        whole, = [e for e in mine if e[0] == "executor.step"]
        for name, start, dur, _ in mine:
            if name != "host.sync":      # the wait comes after the call
                assert whole[1] <= start
                assert start + dur <= whole[1] + whole[2]


def test_dispatch_lead_on_the_recorded_seq128_run():
    """By hand: dispatch 312..315 end at 8,789,129 / 108,122,069 /
    209,851,947 / 309,490,617 ns; the step module starts at 97,255,959 /
    197,262,873 / 297,291,136 / 397,306,599: leads 88,466,830 / 89,140,804
    / 87,439,189 / 87,815,982 ns, median 88,141,406."""
    rec = _recorded("program_spans_seq128.json")
    assert ps.dispatch_leads_ns(rec) == [88466830, 89140804, 87439189,
                                         87815982]
    assert ps.dispatch_lead_ms(rec) == pytest.approx(88.141406)
    # the seven tiny programs of rng_key run between two steps
    small = [m for m in rec["devices"]["/device:TPU:0"]["modules"]
             if not m[0].startswith("jit_step_once")]
    assert len(small) == 21 and max(m[2] for m in small) < 1100
    # a trace that opened after step 312 was dispatched: three pairs
    full = rec["program"]
    rec["program"] = [e for e in full if e[3] != 312]
    assert ps.dispatch_leads_ns(rec) == [89140804, 87439189, 87815982]
    # a dispatch lost in the middle: no number, and the reason
    rec["program"] = [e for e in full if e[3] != 313]
    why = []
    assert ps.dispatch_lead_ms(rec, why) is None
    assert why == ["dispatch annotations of steps 312..315, 3 of them"]


def test_readers_on_the_recorded_four_chip_run(monkeypatch):
    """``spmd.*`` names, four device planes.  Kept steps 16 / 32 / 48 of the
    window, by hand: feed_stage 1.7005 / 3.0053 / 2.0208; rng_key 3.6124 /
    3.5161 / 2.9878; dispatch 14.0233 / 12.9359 / 12.9736; gather_state +
    apply_results 0.3055 + 3.2905 / 0.4733 + 2.9509 / 0.2948 + 2.9608; the
    step less its eight children 24.4313 - 24.2738 / 24.0136 - 23.8245 /
    22.2894 - 22.1341.  Dispatch 240..243 end at 20,126,079 / 150,570,458 /
    282,047,437 / 414,503,375 ns; device 0 starts the step module at
    128,711,674 / 260,591,993 / 392,130,287 / 523,717,402, about 2.5 ms
    before the other three."""
    rec = _recorded("program_spans_dp4.json")
    monkeypatch.setattr(ps, "ring_records", lambda: rec["ring"])
    state = {"t_window": rec["t_window"], "window_s": rec["window_s"]}
    assert _read("feed_stage_ms", state) == 2.0208
    assert _read("rng_key_ms", state) == 3.5161
    assert _read("jit_call_ms", state) == 12.9736
    assert _read("scope_io_ms", state) == pytest.approx(3.4242)
    assert _read("run_unattributed_ms", state) == pytest.approx(
        0.1575, abs=1e-3)
    assert _read("gc_pause_ms_max", state) == 0.0
    leads = ps.dispatch_leads_ns(rec)
    assert len(leads) == 16 and leads[:4] == [
        128711674 - 20126079, 260591993 - 150570458,
        392130287 - 282047437, 523717402 - 414503375]
    assert ps.dispatch_lead_ms(rec) == pytest.approx(111.525888)
    # every phase of every kept step under the runner's prefix
    for step in (240, 241, 242, 243):
        names = sorted(e[0] for e in rec["program"] if e[3] == step)
        assert names == sorted(["host.sync"] + ["spmd." + n for n in (
            "step", "fusion_resolve", "feed_stage", "lookup", "gather_state",
            "rng_key", "dispatch", "apply_results", "finish_fetches")])
    # rng_key's tiny programs run on the first device alone
    tiny = {name: sum(1 for m in dev["modules"]
                      if not m[0].startswith("jit_traced"))
            for name, dev in rec["devices"].items()}
    assert tiny == {"/device:TPU:0": 42, "/device:TPU:1": 0,
                    "/device:TPU:2": 0, "/device:TPU:3": 0}
    # one device short of its last execution (the trace closed): that
    # device pairs three steps; short of one in the middle: no number
    last = rec["devices"]["/device:TPU:3"]["modules"].pop()
    assert len(ps.dispatch_leads_ns(rec)) == 15
    rec["devices"]["/device:TPU:3"]["modules"].append(last)
    del rec["devices"]["/device:TPU:3"]["modules"][1]
    why = []
    assert ps.dispatch_lead_ms(rec, why) is None
    assert why == ["/device:TPU:3: 3 executions of the step module for 4 "
                   "steps dispatched"]


# -- the live program --------------------------------------------------------

def test_readers_on_the_live_ring_of_a_bert_tiny_loop():
    """Through the loop the cells run: every kept step of the window gives
    each host metric something to read."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.observability import tracing

    sys.path.insert(0, HERE)
    from test_chipbench import TINY, TINY_TRAFFIC

    tracing.reset_tracing()
    builder = mf.load_by_name("builders", "bert")
    driver = mf.load_by_name("traffic", "train_loop")
    startup, program, loss, _ = builder.build(
        TINY, {"fused_ln": True, "fused_qkv": True}, TINY_TRAFFIC, 7)
    pools = builder.make_pools(TINY, TINY_TRAFFIC, np.random.default_rng(7))
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        state = driver.measure(exe, program, loss, pools, TINY_TRAFFIC, 1.0)
    kept = ps.kept_steps(ps.in_window(ps.ring_records(), state))
    assert len(kept) >= state["steps"] // 16 >= 1
    for step, phases in kept:
        assert step["attrs"]["lazy"] is True
        assert {"fusion_resolve", "feed_stage", "lookup", "gather_state",
                "rng_key", "dispatch", "apply_results",
                "finish_fetches"} <= set(phases)
        stage = phases["feed_stage"][0]["attrs"]
        # six distinct tiny batches: the FeedCache holds them after a lap
        assert stage["hits"] + stage["misses"] >= 1
        assert stage["bytes"] > 0 or not stage["misses"]
    values = {m: _read(m, state) for m in NEW_METRICS}
    assert values.pop("dispatch_lead_ms") is None      # no device trace
    assert values.pop("gc_pause_ms_max") >= 0.0
    assert all(v > 0 for v in values.values()), values
    # each kept step lies inside one call of the loop, and its phases
    # inside it, one after another (times on a busy CPU say no more)
    inside = state["dispatch_s"][state["first"]:
                                 state["first"] + state["steps"]]
    for step, phases in kept:
        assert step["dur_ms"] <= 1e3 * max(inside)
        covered = sum(c["dur_ms"] for kids in phases.values() for c in kids)
        assert covered <= step["dur_ms"] + 1e-3
