"""What the program itself says of a step, read two ways.

**The ring** (the five host metrics and ``gc_pause_ms_max``): the program
keeps the span tree of some steps (``paddle_tpu.observability.tracing``:
one in 16, and every slow one) in memory.  :func:`kept_steps` takes the
records, cuts them to the measured window by their ``t0_ns``
(``time.perf_counter_ns()``, the clock of the loop's ``t_window`` and
``window_s``) and hands each step back with its children, under either
runner's prefix (``executor.`` or ``spmd.``).

**The xplane** (``dispatch_lead_ms``): every phase of every step is also a
``TraceAnnotation`` with the step's number on the host plane of the traced
window, on the device ops' clock.  :func:`read_annotations` turns the
``.xplane.pb`` into a small plain structure, so that the reduction is
checked on the trimmed recordings under ``testdata/`` without a chip::

    {"devices": {"/device:TPU:0": {"modules": [[name, start_ns, dur_ns]..]}},
     "program": [[name, start_ns, dur_ns, step]..]}    # the program's phases

A program that has no such spans (the parent of the PR that brought them)
gives every reader here nothing to read: they return None and do not raise.
"""

import os
import statistics

from chipbench import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
# where run.py keeps the traced window (its TRACE_DIR)
TRACE_DIR = os.path.join(os.path.dirname(HERE), ".chipbench_trace")
RUNNERS = ("executor", "spmd")
PROGRAM_PREFIXES = tuple(r + "." for r in RUNNERS) + ("host.",)


# -- the ring ----------------------------------------------------------------

def ring_records():
    """The program's closed spans, oldest first; [] where the program has
    no tracer."""
    try:
        from paddle_tpu.observability import tracing
        return tracing.get_tracer().records()
    except (ImportError, AttributeError):
        return []


def in_window(records, state):
    """The records that start inside the measured window.  None where no
    record carries a monotonic start: the program cannot be cut to a
    window, whatever it recorded."""
    if not any("t0_ns" in r for r in records):
        return None
    lo = state["t_window"] * 1e9
    hi = lo + state["window_s"] * 1e9
    return [r for r in records if lo <= r.get("t0_ns", -1) < hi]


def kept_steps(records):
    """``[(step record, {phase: [child records]})]`` of the ``*.step``
    spans among ``records``; a phase is a direct child's name less the
    runner's prefix (``executor.feed_stage`` is ``feed_stage``), and
    ``host.gc`` keeps its name."""
    children = {}
    for r in records:
        children.setdefault(r.get("parent"), []).append(r)
    steps = []
    for r in records:
        runner, _, what = r["name"].partition(".")
        if what != "step" or runner not in RUNNERS:
            continue
        phases = {}
        for child in children.get(r["span"], ()):
            name = child["name"]
            if name.startswith(runner + "."):
                name = name[len(runner) + 1:]
            phases.setdefault(name, []).append(child)
        steps.append((r, phases))
    return steps


def phase_ms(state, *phases):
    """Median over the window's kept steps of the time in ``phases``
    together; None where no kept step has all of them."""
    records = in_window(ring_records(), state)
    if records is None:
        return None
    per_step = [sum(c["dur_ms"] for p in phases for c in children[p])
                for _, children in kept_steps(records)
                if all(p in children for p in phases)]
    return statistics.median(per_step) if per_step else None


def self_ms(state):
    """Median over the window's kept steps of the step's self time: its
    duration less what its children cover."""
    records = in_window(ring_records(), state)
    if records is None:
        return None
    per_step = []
    for step, children in kept_steps(records):
        if not children:
            continue
        lo = step["t0_ns"]
        hi = lo + step["dur_ms"] * 1e6
        covered = xplane.union(
            [max(lo, c["t0_ns"]), min(hi, c["t0_ns"] + c["dur_ms"] * 1e6)]
            for kids in children.values() for c in kids)
        per_step.append(step["dur_ms"] - xplane.length(covered) / 1e6)
    return statistics.median(per_step) if per_step else None


def longest_gc_ms(state):
    """The longest ``host.gc`` pause that starts in the window; 0.0 where
    the program records them and recorded none."""
    records = in_window(ring_records(), state)
    if records is None:
        return None
    return max((r["dur_ms"] for r in records if r["name"] == "host.gc"),
               default=0.0)


# -- the xplane --------------------------------------------------------------

def read_annotations(path):
    """The structure above from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    out = {"devices": {}, "program": []}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    out["devices"][plane.name] = {"modules": [
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events]}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIXES):
                        step = dict(e.stats).get("step")
                        out["program"].append(
                            [e.name, e.start_ns, e.duration_ns,
                             None if step is None else int(step)])
    out["program"].sort(key=lambda e: e[1])
    return out


def step_executions(dev):
    """``[(start_ns, end_ns)]`` of the step module on one device, in
    order: the module with the most device time (``xplane.steady_window``'s
    choice)."""
    by_name = {}
    for name, start, dur in dev["modules"]:
        by_name.setdefault(name, []).append((start, start + dur))
    return sorted(max(by_name.values(), key=xplane.length)) \
        if by_name else []


def dispatch_leads_ns(trace, why=None):
    """Per step and device: the start of the step module's execution less
    the end of that step's ``*.dispatch`` annotation.

    **Paired by order, not by clock.**  A device runs what it is handed in
    order, so on each device the k-th execution that has its dispatch in
    the trace is the k-th dispatched step.  Only the ends can lack a
    partner: executions in front that were handed over before the trace
    opened, and dispatches behind whose step ran after it closed.  The
    counts say how many: every execution over the number of steps is a
    stranger in front, and each step left without an execution was
    dispatched last.

    The clock only checks that, and never to the millisecond: the device
    plane's stamps lie 0.4-1.9 ms early against the host plane's, and the
    first traced step starts on an idle device 1.3-2.9 ms after its
    dispatch begins (PERF.md 6, PRs 26-29), so "this execution began
    before that dispatch did" reads the wrong way round on some runs (the
    reader that turned on it refused PRs 28 and 34).  No comparison of a
    device stamp with a host stamp here turns on less than an allowance
    taken from the trace itself, a quarter of the median execution of the
    step module: far over any skew, far under the whole step by which a
    wrong alignment is off.  Beyond it: an execution that began before the
    first dispatch did is one more stranger; a stranger by the counts has
    to have begun before the first dispatch did, a dispatch dropped behind
    after the last execution did, and a pair's execution after its own
    dispatch did, or the alignment is off.  A lead that the skew makes
    slightly negative is reported as it reads.

    None, with the reason appended to ``why``, for a fault of the program
    or the trace: no annotation, one without a step or twice, a step
    missing in the middle, no device plane, counts that the ends do not
    explain, an alignment a whole step off."""
    why = [] if why is None else why
    dispatches = {}
    for name, start, dur, step in trace["program"]:
        if name.endswith(".dispatch") and name.startswith(
                tuple(r + "." for r in RUNNERS)):
            if step is None or step in dispatches:
                why.append("a %s annotation %s" % (
                    name, "without a step" if step is None
                    else "twice for step %d" % step))
                return None
            dispatches[step] = (start, start + dur)
    if not dispatches or not trace["devices"]:
        why.append("no *.dispatch annotation of the program" if not dispatches
                   else "no device plane with executions")
        return None
    steps = sorted(dispatches)
    if steps != list(range(steps[0], steps[-1] + 1)):
        why.append("dispatch annotations of steps %d..%d, %d of them"
                   % (steps[0], steps[-1], len(steps)))
        return None
    ordered = [dispatches[s] for s in steps]
    leads = []
    for dev_name, dev in sorted(trace["devices"].items()):
        runs = step_executions(dev)
        if not runs:
            why.append("%s: no execution of the step module" % dev_name)
            return None
        allow = statistics.median(end - start for start, end in runs) / 4
        front = max(0, len(runs) - len(ordered))
        if front and runs[front - 1][0] > ordered[0][0] + allow:
            why.append("%s: %d executions of the step module for %d steps "
                       "dispatched, and the first %d did not begin before "
                       "the first dispatch" % (dev_name, len(runs),
                                               len(ordered), front))
            return None
        while front < len(runs) and \
                runs[front][0] < ordered[0][0] - allow:
            front += 1
        paired = runs[front:]
        behind = ordered[len(paired):]
        if not paired or (behind and
                          behind[0][0] < paired[-1][0] - allow):
            why.append("%s: %d executions of the step module for %d steps "
                       "dispatched" % (dev_name, len(paired), len(ordered)))
            return None
        for (d_start, d_end), (r_start, _) in zip(ordered, paired):
            if r_start < d_start - allow:
                why.append("%s: an execution at %d ns, over the allowance "
                           "of %d before its dispatch began at %d"
                           % (dev_name, r_start, allow, d_start))
                return None
            leads.append(r_start - d_end)
    return leads


def dispatch_lead_ms(trace, why=None):
    leads = dispatch_leads_ns(trace, why)
    return statistics.median(leads) / 1e6 if leads else None


def traced_annotations():
    """The newest traced window's annotations, or None without one."""
    path = xplane.newest_xplane(TRACE_DIR)
    return read_annotations(path) if path else None
