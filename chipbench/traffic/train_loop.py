"""The loop every training cell runs, and everything else that belongs to
a training cell: set-up, the window's metrics, and what decides ``correct``.

``run.py`` calls ``drive`` (set-up, the measured window, the traced window)
and, after it has read the device's memory, ``verify`` (outside the window:
the losses, the compiled step, the plain reference).  A driver for another
kind of traffic is another file here with the same two functions.

A ring of host pools is made from the seed during set-up.  Each step feeds
the next batch as NumPy through the public ``Executor.run(feed=...,
fetch_list=[loss], return_numpy=False)``, so the host-to-device copy and
the dispatch are inside the step; after dispatching step i+1 the loop
blocks on step i's loss handle and takes the clock.  That is one step in
flight, one completion sample per step, no drained pipeline, and a
finite-loss check on every step.

Every step's batch is a new host array: pool ``i % ring`` from row
``(i // ring) % offsets``, ``ring * offsets`` distinct batches in all.  A
trainer's data is new each step, and the program's ``FeedCache`` (64
entries, keyed by content) would otherwise keep a ring of 8 on the device
after its first lap and the feed would never be measured.
"""

import math
import statistics
import time

import numpy as np

from chipbench import manifest as mf
from chipbench import xplane


class Batches:
    """Step i's feed: views into the pools, no copy on the host."""

    def __init__(self, pools, batch, offsets):
        self.pools, self.batch, self.offsets = pools, batch, offsets
        self.i = 0

    def next(self):
        pool = self.pools[self.i % len(self.pools)]
        off = (self.i // len(self.pools)) % self.offsets
        self.i += 1
        return {n: a[off:off + self.batch] for n, a in pool.items()}


class CompileCounter:
    """Counts JAX's own compile events (``jax.monitoring``), so that the
    window can show it compiled nothing: ``compiles`` are backend compiles
    and persistent-cache retrievals, ``traces`` are re-traces of a jaxpr
    (host work only; on the TPU the program's ``rng_key`` re-traces one
    small function every step).  ``compiled`` names each function that
    went to the backend and the seconds it took there."""

    def __init__(self):
        import jax

        self.compiles = self.traces = 0
        self.compiled = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            self.compiled.append((kw.get("fun_name"), duration))
        if event.endswith("backend_compile_duration") \
                or "cache_retrieval" in event:
            self.compiles += 1
        elif event.endswith("jaxpr_trace_duration"):
            self.traces += 1


class Loop:
    def __init__(self, exe, program, loss, batches):
        from jax.profiler import TraceAnnotation

        self._exe, self._program, self._loss = exe, program, loss
        self._batches, self._span = batches, TraceAnnotation
        self.dispatch_s = []     # host seconds inside Executor.run, per step
        self.losses = []         # every completed step's loss, in order

    def dispatch(self):
        with self._span("chipbench.feed"):
            feed = self._batches.next()
        t0 = time.perf_counter()
        with self._span("chipbench.dispatch"):
            handle = self._exe.run(self._program, feed=feed,
                                   fetch_list=[self._loss],
                                   return_numpy=False)[0]
        self.dispatch_s.append(time.perf_counter() - t0)
        return handle

    def wait(self, handle):
        """Blocks until that step's loss is on the host; returns the clock."""
        with self._span("chipbench.wait_loss"):
            self.losses.append(float(np.asarray(handle).reshape(-1)[0]))
        return time.perf_counter()

    def step_sync(self):
        """One step, dispatched and completed: for warm-up."""
        return self.wait(self.dispatch())

    def run(self, seconds=None, steps=None):
        """Steps with one in flight until ``seconds`` have passed at a
        completion (or ``steps`` have completed).  The window opens at the
        completion of a step that is not counted, with the next already
        dispatched, and closes at a completion.  Returns the intervals
        between successive completions and the index of the window's first
        step in ``losses`` and ``dispatch_s``."""
        prev = self.dispatch()
        nxt = self.dispatch()
        t_open = last = self.wait(prev)
        first = len(self.losses)
        prev, intervals = nxt, []
        while True:
            nxt = self.dispatch()
            now = self.wait(prev)
            intervals.append(now - last)
            last, prev = now, nxt
            if steps is not None and len(intervals) >= steps:
                break
            if seconds is not None and now - t_open >= seconds:
                break
        window_s = last - t_open
        self.wait(prev)          # the step in flight, outside the window
        return {"intervals_s": intervals, "window_s": window_s,
                "first": first, "steps": len(intervals)}


def quantile(values, q):
    """Linear-interpolated quantile of a non-empty list."""
    s = sorted(values)
    pos = q * (len(s) - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def measure(exe, program, loss, pools, traffic, seconds, trace_dir=None,
            counters=dict):
    """Warm-up, the measured window and, where ``trace_dir`` is given, a
    traced window of ``trace_steps`` steps after it.  Returns the loop's
    clocks and losses, and under ``counters`` what ``counters()`` (the
    program's device counters through the configuration's ``flops``
    module; nothing for a configuration without any) read where the
    window opened (``window``: a host sync before ``t_window``, so inside
    set-up), where the traced window opened (``trace``) and where it
    closed (``end``): a reader takes one from another and so counts a
    window's steps alone."""
    import jax

    compiles = CompileCounter()
    loop = Loop(exe, program, loss,
                Batches(pools, traffic["batch"], traffic["offsets"]))
    t0 = time.perf_counter()
    loop.step_sync()                       # compiles, or hits the cache
    compile_s = time.perf_counter() - t0
    warmup = []                  # per warm-up step: seconds, what compiled
    for _ in range(traffic["warmup_steps"]):
        t0, n0 = time.perf_counter(), len(compiles.compiled)
        loop.step_sync()
        warmup.append({"s": time.perf_counter() - t0,
                       "compiled": compiles.compiled[n0:]})
    before = compiles.compiles, compiles.traces
    counted = {"window": counters()}
    t_window = time.perf_counter()
    window = loop.run(seconds=seconds)
    out = dict(window, compile_s=compile_s, t_window=t_window,
               warmup=warmup, counters=counted,
               compiles_in_window=compiles.compiles - before[0],
               traces_in_window=compiles.traces - before[1],
               losses=loop.losses, dispatch_s=loop.dispatch_s)
    if trace_dir is not None:
        # the Python tracer would slow the very host code the gaps are
        # attributed to; the loop's own spans need only the host tracer
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        counted["trace"] = counters()
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            out["traced"] = loop.run(steps=traffic["trace_steps"])
        finally:
            jax.profiler.stop_trace()
        counted["end"] = counters()
    return out


def drive(cell, seed, seconds, trace_dir=None, t_start=None):
    """Set-up from the cell's data and ``seed``, then ``measure``.  Returns
    the state ``verify`` and the per-layer readers go on from; its
    ``metrics`` are the end-to-end values this kind of traffic yields
    (``run.py`` adds ``setup_s``, which ends at ``t_window``)."""
    import paddle_tpu as fluid
    from paddle_tpu.static_analysis import fusion

    workload, config, traffic = (cell["workload"], cell["config"],
                                 cell["traffic"])
    builder = mf.load_by_name("builders", config["builder"])
    t_import = time.perf_counter()
    startup, program, loss, main_program = builder.build(
        config, workload["program"], traffic, seed)
    fusion.resolve_fused_program(main_program, targets=[loss.name])
    t_build = time.perf_counter()
    pools = builder.make_pools(config, traffic, np.random.default_rng(seed))
    t_pools = time.perf_counter()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(startup)                       # the weights, on the device
    t_weights = time.perf_counter()

    flops = mf.load_by_name("flops", config["flops"]) \
        if "flops" in config else None
    counters = (lambda: flops.counted_rows(config)) \
        if hasattr(flops, "counted_rows") else dict
    state = measure(exe, program, loss, pools, traffic, seconds, trace_dir,
                    counters)
    intervals = state["intervals_s"]
    state["window_losses"] = state["losses"][
        state["first"]:state["first"] + state["steps"]]
    rate = state["steps"] * traffic["batch"] / state["window_s"]
    state.update(
        exe=exe, builder=builder, pools=pools, seed=seed,
        main_program=main_program,
        metrics={"train_examples_per_s": rate,
                 "step_ms_p95": 1e3 * quantile(intervals, 0.95)},
        attempted=state["steps"],
        failed=sum(1 for x in state["window_losses"]
                   if not math.isfinite(x)),
        clocks={"import_s": t_import - (t_start or t_import),
                "build_s": t_build - t_import, "pools_s": t_pools - t_build,
                "weights_s": t_weights - t_pools,
                "compile_s": state["compile_s"],
                "warmup_s": state["t_window"] - t_weights
                - state["compile_s"]},
        report={"steps": state["steps"], "window_s": state["window_s"],
                "step_ms_median": 1e3 * statistics.median(intervals),
                "step_ms_max": 1e3 * max(intervals),
                "tokens_per_s": rate * traffic["seq_len"]
                if "seq_len" in traffic else None,
                "warmup_steps": state["warmup"],
                "jaxpr_traces_in_window": state["traces_in_window"]})
    return state


def relower_last_step(feed, seed):
    """The step the Executor compiled last, lowered again for its text and
    its memory analysis: a hit in the persistent cache, since the same
    module was compiled a moment ago (as ``chip_smoke.py`` does it).  The
    program has no public way to the compiled step, so this reads its
    internals, and says which when they have moved."""
    import paddle_tpu as fluid
    from paddle_tpu import executor

    try:
        block = executor._LAST_COMPILED_BLOCK
        scope = fluid.global_scope()
        rw = {n: scope.get(n) for n in block.rw_names}
        ro = {n: scope.get(n) for n in block.ro_names}
        feed = {n: feed[n] for n in block.feed_names}
        return block.jitted.lower(feed, rw, ro,
                                  executor.rng_key(seed)).compile()
    except (AttributeError, TypeError, KeyError) as e:
        raise RuntimeError(
            "chipbench reads the compiled step through paddle_tpu.executor."
            "_LAST_COMPILED_BLOCK (.jitted, .rw_names, .ro_names, "
            ".feed_names) and executor.rng_key(seed), lowered as "
            "jitted.lower(feed, rw, ro, key); the program has changed one "
            "of them (%s: %s).  A benchmark PR has to follow it in "
            "chipbench/traffic/train_loop.py relower_last_step."
            % (type(e).__name__, e)) from e


def check_step(compiled, workload, devices, main_program):
    """Kernels and collectives of the compiled step against what the cell
    is there for.  Returns ``(problems, kernels, all_reduces, memory,
    op_names)``."""
    import paddle_tpu as fluid
    from paddle_tpu.ops.pallas import pallas_kernels_in

    text = compiled.as_text()
    kernels = dict(pallas_kernels_in(text))
    problems = []
    for want in workload["require_kernels"]:
        if not any(want in k for k in kernels):
            problems.append("no %s kernel in the compiled step" % want)
    all_reduces = text.count(" all-reduce")
    if workload["require_collectives"]:
        if not all_reduces:
            problems.append("no all-reduce in the compiled step")
        scope = fluid.global_scope()
        spans = {len(scope.get(p.name).sharding.device_set)
                 for p in main_program.global_block().all_parameters()}
        if spans != {len(devices)}:
            problems.append("parameters span %s devices, not %d"
                            % (sorted(spans), len(devices)))
    mem = compiled.memory_analysis()
    memory = {k: getattr(mem, k + "_size_in_bytes", None)
              for k in ("argument", "output", "temp", "alias",
                        "generated_code")} if mem is not None else {}
    return problems, kernels, all_reduces, memory, xplane.op_names_in(text)


def check_losses(first_loss, window, band):
    """``window`` are the losses of the measured steps.  Returns the
    problems and the means of the window's first and last tenth."""
    problems = []
    if not all(math.isfinite(x) for x in [first_loss] + window):
        problems.append("a loss is not finite")
    if not band[0] <= first_loss <= band[1]:
        problems.append("first loss %.4f outside the untrained band %s"
                        % (first_loss, band))
    tenth = max(1, len(window) // 10)
    head = statistics.fmean(window[:tenth])
    tail = statistics.fmean(window[-tenth:])
    if not tail < head:
        problems.append("loss did not fall over the window: first tenth "
                        "%.4f, last tenth %.4f" % (head, tail))
    return problems, head, tail


def distance(kind, got, want):
    """How far ``got`` lies from the reference's ``want``: ``rel_l2`` is the
    norm of the difference over the norm of ``want``, ``abs`` the largest
    difference."""
    got = np.asarray(got, "float64").reshape(-1)
    want = np.asarray(want, "float64").reshape(-1)
    if got.shape != want.shape:
        return float("inf")
    if kind == "rel_l2":
        return float(np.linalg.norm(got - want) / np.linalg.norm(want))
    if kind == "abs":
        return float(np.max(np.abs(got - want)))
    raise ValueError("no distance named %r" % kind)


def compare_with_reference(exe, builder, cell, pools):
    """The program's forward in test mode against the configuration's plain
    reference (``reference/<module>.py``: float32 ``jax.numpy`` at the
    highest matmul precision), on the weights as the window left them and
    the first ``examples`` rows of the first pool.  Returns the problems
    and each distance measured."""
    import jax

    import paddle_tpu as fluid

    config = cell["config"]
    spec = config.get("reference")
    if not spec:
        return ["the configuration names no plain reference"], {}
    reference = mf.load_by_name("reference", spec["module"])
    ev = builder.build_eval(config, cell["workload"]["program"],
                            cell["traffic"])
    rows = {n: a[:spec["examples"]] for n, a in pools[0].items()}
    names = sorted(ev["fetch"])
    got = exe.run(ev["program"], feed={n: rows[n] for n in ev["feeds"]},
                  fetch_list=[ev["fetch"][n] for n in names])
    scope = fluid.global_scope()
    weights = {n: np.asarray(scope.get(n)) for n in ev["weights"]}
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda w, f: reference.forward(w, f, config))(
            weights, rows)
    problems, measured = [], {}
    for name, limits in spec["tolerance"].items():
        for kind, limit in limits.items():
            d = distance(kind, got[names.index(name)], want[name])
            measured["%s_%s" % (name, kind)] = d
            if not d <= limit:
                problems.append("%s is %s %.4g from the plain reference, "
                                "over %g" % (name, kind, d, limit))
    return problems, measured


def compared_with_limits(config, first_loss, head, tail, distances):
    """Each number ``verify`` compared beside its limit, under short plain
    names, for the result's line and the run's last lines."""
    band = config["loss_band_first_step"]
    out = {"loss_first": {"value": first_loss, "min": band[0],
                          "max": band[1]},
           "loss_last_tenth": {"value": tail, "below": head}}
    for name, limits in config.get("reference", {}).get(
            "tolerance", {}).items():
        for kind, limit in limits.items():
            key = "%s_%s" % (name, kind)
            if key in distances:
                out[key] = {"value": distances[key], "max": limit}
    return out


def verify(state, cell, devices):
    """Outside the window: the losses, the compiled step and the plain
    reference.  Returns the problems; adds ``kernels`` and ``op_names`` (for
    the trace reduction), ``compared`` (each number beside its limit) and
    its findings to ``state``."""
    workload, config, traffic = (cell["workload"], cell["config"],
                                 cell["traffic"])
    t0 = time.perf_counter()
    # before the reference check: its test-mode program becomes the step
    # "compiled last"
    feed = {n: a[:traffic["batch"]] for n, a in state["pools"][0].items()}
    problems, kernels, all_reduces, memory, op_names = check_step(
        relower_last_step(feed, state["seed"]), workload, devices,
        state["main_program"])
    t1 = time.perf_counter()
    more, head, tail = check_losses(state["losses"][0],
                                    state["window_losses"],
                                    config["loss_band_first_step"])
    problems += more
    if state["compiles_in_window"]:
        problems.append("%d compilations inside the window"
                        % state["compiles_in_window"])
    more, distances = compare_with_reference(
        state["exe"], state["builder"], cell, state["pools"])
    problems += more
    state.update(kernels=kernels, op_names=op_names,
                 compared=compared_with_limits(
                     config, state["losses"][0], head, tail, distances))
    state["report"].update(
        loss_first=state["losses"][0], loss_window_first_tenth=head,
        loss_window_last_tenth=tail, kernels=kernels,
        all_reduces=all_reduces, compiled_memory_bytes=memory,
        reference=distances, relower_for_check_s=t1 - t0,
        reference_check_s=time.perf_counter() - t1)
    return problems
