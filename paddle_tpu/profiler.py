"""Profiler: host event recorder + device tracer bridge + timeline export.

Reference surfaces reproduced:
* ``platform/profiler.h`` — RAII ``RecordEvent`` wrapped around every op
  run, thread-local ``EventList``, ``EnableProfiler/DisableProfiler``
  printing tables aggregated by total/max/ave/calls.  Here host events
  come from ``record_event`` scopes and the runners' step phases
  (``observability.tracing.phase``: ``executor.step`` and its children
  ``feed_stage`` / ``rng_key`` / ``dispatch`` / ..., ``host.sync`` with
  its ``executor.device_compute`` / ``executor.host_sync`` split
  :func:`host_event_stats` documents) — per-op host
  timing does not exist under a whole-block jit, so phases are the
  host-side unit of accounting (the per-op cost lives in the device
  trace, which XLA annotates with HLO op names).
* ``tools/timeline.py:115-161`` — chrome://tracing JSON; written directly
  by ``stop_profiler`` from the recorded host events.
* device side: ``jax.profiler`` (XPlane → TensorBoard), the CUPTI
  ``DeviceTracer`` analogue; ``record_event`` doubles as a
  ``jax.profiler.TraceAnnotation`` so user scopes appear in device traces.
"""

import contextlib
import json
import re
import tempfile
import threading
import time

__all__ = ["profiler", "start_profiler", "stop_profiler", "reset_profiler",
           "record_event", "cuda_profiler", "is_profiler_enabled",
           "attribute_op_name", "device_op_stats", "device_op_events",
           "host_event_stats", "export_chrome_trace"]

_trace_dir = None
_enabled = False
_events = []          # (name, tid, t0_us, t1_us)
_events_lock = threading.Lock()
_device_trace = False


def is_profiler_enabled():
    return _enabled


def start_profiler(state="All", tracer_option=None):
    """state: 'CPU' → host events only; 'GPU'/'All' → also start the jax
    device tracer (reference profiler.py:127 semantics, GPU≈device)."""
    global _enabled, _trace_dir, _device_trace
    reset_profiler()
    _enabled = True
    _device_trace = state in ("GPU", "All")
    if _device_trace:
        import jax

        _trace_dir = tempfile.mkdtemp(prefix="paddle_tpu_profile_")
        try:
            jax.profiler.start_trace(_trace_dir)
        except Exception:
            _device_trace = False


def _aggregate():
    table = {}
    with _events_lock:
        evs = list(_events)
    for name, tid, t0, t1 in evs:
        row = table.setdefault(name, [0, 0.0, 0.0, None])
        dt = (t1 - t0) / 1000.0  # ms
        row[0] += 1
        row[1] += dt
        row[2] = max(row[2], dt)
        row[3] = dt if row[3] is None else min(row[3], dt)
    return table


def host_event_stats():
    """Aggregated host events while profiling is (or was) on:
    ``{name: {"calls", "total_ms", "max_ms", "min_ms"}}``.  Beside the
    other phases of ``executor.step``, a profile has
    ``executor.dispatch`` (enqueue under async
    dispatch), ``executor.device_compute`` (waiting for the in-flight
    step at a sync point) and ``executor.host_sync`` (D2H copies) — so
    ``dispatch ≪ device_compute`` in a profile means the loop overlaps,
    while a large per-step ``host_sync`` total flags a loop that blocks
    every iteration (the r05 infer pathology)."""
    return {
        name: {"calls": calls, "total_ms": total, "max_ms": mx,
               "min_ms": mn or 0.0}
        for name, (calls, total, mx, mn) in _aggregate().items()
    }


def _print_summary(sorted_key):
    table = _aggregate()
    if not table:
        return
    keyfn = {
        None: lambda kv: -kv[1][1],
        "default": lambda kv: -kv[1][1],
        "total": lambda kv: -kv[1][1],
        "calls": lambda kv: -kv[1][0],
        "max": lambda kv: -kv[1][2],
        "min": lambda kv: kv[1][3],
        "ave": lambda kv: -(kv[1][1] / kv[1][0]),
    }.get(sorted_key, lambda kv: -kv[1][1])
    rows = sorted(table.items(), key=keyfn)
    name_w = max(len("Event"), *(len(n) for n, _ in rows)) + 2
    print("\n------------------------->  Profiling Report  "
          "<-------------------------\n")
    print("%-*s %-8s %-12s %-12s %-12s %-12s" % (
        name_w, "Event", "Calls", "Total(ms)", "Max(ms)", "Min(ms)",
        "Ave(ms)"))
    for name, (calls, total, mx, mn) in rows:
        print("%-*s %-8d %-12.4f %-12.4f %-12.4f %-12.4f" % (
            name_w, name, calls, total, mx, mn or 0.0, total / calls))
    print()


def _write_chrome_trace(path, device_events=None, spans=None):
    """chrome://tracing 'traceEvents' JSON (tools/timeline.py output
    format: X (complete) events with microsecond timestamps).

    ``device_events`` — parsed :func:`device_op_events` rows
    ``(op_name, ts_us, dur_us, line_name)`` — render as pid 1 with one
    tid per device line, so the device stream sits next to the host
    phase events instead of being silently dropped.

    ``spans`` — tracing span records — render as per-rank span
    processes with flow arrows (cross-thread/rank causality), plus a
    flow arrow from each dispatch-shaped span to the first device op
    launched after it, so a serving request's span visibly leads to
    the device ops it ran — ONE file for all three streams."""
    events = []
    with _events_lock:
        evs = list(_events)
    for name, tid, t0, t1 in evs:
        events.append({
            "name": name, "cat": "paddle_tpu", "ph": "X",
            "pid": 0, "tid": tid, "ts": t0, "dur": t1 - t0,
        })
    line_tids = {}
    if device_events:
        events.append({"name": "process_name", "ph": "M", "pid": 1,
                       "args": {"name": "device"}})
        for name, ts, dur, line in device_events:
            tid = line_tids.setdefault(line, len(line_tids))
            events.append({
                "name": name, "cat": "device", "ph": "X",
                "pid": 1, "tid": tid, "ts": ts, "dur": dur,
            })
        for line, tid in line_tids.items():
            events.append({"name": "thread_name", "ph": "M", "pid": 1,
                           "tid": tid, "args": {"name": line}})
    if spans:
        from .observability.tracing import spans_to_chrome_events

        events.extend(spans_to_chrome_events(spans))
        if device_events:
            events.extend(_span_device_flows(spans, device_events,
                                             line_tids))
    with open(path, "w") as f:
        json.dump({"traceEvents": events,
                   "displayTimeUnit": "ms"}, f)


def _span_device_flows(spans, device_events, line_tids):
    """Best-effort flow arrows dispatch-span → first device op at or
    after the span's start (both clocks are wall-epoch µs, so 'the op
    this dispatch launched' is the nearest subsequent event)."""
    out = []
    dev = sorted((ts, name, line) for name, ts, dur, line
                 in device_events)
    if not dev:
        return out
    starts = [d[0] for d in dev]
    import bisect

    for r in spans:
        if r.get("ts") is None \
                or not str(r.get("name", "")).endswith(".dispatch"):
            continue
        ts_us = float(r["ts"]) * 1e6
        i = bisect.bisect_left(starts, ts_us)
        if i >= len(dev):
            continue
        dts, _dname, dline = dev[i]
        fid = "dev/%s" % r.get("span")
        out.append({"name": "launch", "cat": "span-device", "ph": "s",
                    "id": fid, "pid": "rank%s" % r.get("rank", 0),
                    "tid": r.get("thread", "main"), "ts": ts_us})
        out.append({"name": "launch", "cat": "span-device", "ph": "f",
                    "bp": "e", "id": fid, "pid": 1,
                    "tid": line_tids.get(dline, 0), "ts": dts})
    return out


def _collect_device_events():
    """Best-effort device rows from the session's trace dir ([] when
    there is no device trace or the xplane can't be parsed)."""
    if _trace_dir is None:
        return []
    try:
        return device_op_events(_trace_dir)
    except Exception:  # noqa: BLE001 - merge is best-effort
        return []


def _collect_spans():
    """This process's span records (closed ring + open snapshots) from
    the live tracer — [] when tracing is disabled or nothing recorded."""
    try:
        from .observability import tracing as _tracing

        if not _tracing.tracing_enabled():
            return []
        tracer = _tracing.get_tracer()
        return tracer.records() + tracer.open_spans()
    except Exception:  # noqa: BLE001 - merge is best-effort
        return []


def export_chrome_trace(path):
    """Write the merged host+device+span chrome trace for the current
    (or just-stopped) profiler session.  Returns ``path``, or None when
    there is nothing to export."""
    with _events_lock:
        have_host = bool(_events)
    device_events = _collect_device_events()
    spans = _collect_spans()
    if not have_host and not device_events and not spans:
        return None
    _write_chrome_trace(path, device_events=device_events, spans=spans)
    return path


# ---------------------------------------------------------------------------
# Device-side per-op attribution (reference profiler.h:166 tables)
#
# The Executor wraps every op lowering in jax.named_scope("pd<idx>_<type>")
# (executor._run_ops_into_env), which XLA carries into HLO op metadata and
# the profiler into XPlane event stats.  These helpers map device-plane
# rows back to Program ops and aggregate the reference-style
# total/max/ave/calls table — per-op timing the whole-block jit cannot
# provide host-side.
# ---------------------------------------------------------------------------

_PD_SCOPE_RE = re.compile(r"pd(\d+)_([A-Za-z0-9_.]+?)(?:/|$)")


def attribute_op_name(s):
    """Extract the INNERMOST ``pd<idx>_<type>`` Program-op tag from an
    HLO metadata / scope path; returns (op_type, idx) or None."""
    m = None
    for m in _PD_SCOPE_RE.finditer(s or ""):
        pass
    if m is None:
        return None
    return m.group(2), int(m.group(1))


def _event_strings(plane, ev, metadata):
    """Every string on an XPlane event that might carry the scope path:
    the event metadata name/display_name plus all string-valued stats
    (schema varies across backends/profiler versions)."""
    out = [metadata.name, metadata.display_name]
    stat_names = plane.stat_metadata
    for stat in list(ev.stats) + list(metadata.stats):
        if stat.str_value:
            out.append(stat.str_value)
        elif stat.ref_value and stat.ref_value in stat_names:
            out.append(stat_names[stat.ref_value].name)
    return [s for s in out if s]


def _iter_device_xla_events(trace_dir):
    """Yield ``(raw_name, tag_or_None, ts_us, dur_us, line_label)`` for
    every device XLA-op event in the newest xplane under ``trace_dir``
    — the ONE parsing/attribution pipeline behind both the aggregate
    table (:func:`device_op_stats`) and the timeline rows
    (:func:`device_op_events`)."""
    import glob
    import os

    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    xplanes = glob.glob(trace_dir + "/**/*.xplane.pb", recursive=True)
    if not xplanes:
        return
    space = xplane_pb2.XSpace()
    with open(max(xplanes, key=os.path.getmtime), "rb") as f:
        space.ParseFromString(f.read())
    for plane in space.planes:
        if "TPU" not in plane.name and "/device:" not in plane.name:
            continue
        ev_meta = plane.event_metadata
        for line in plane.lines:
            if "XLA Ops" not in line.name and line.name != "Ops":
                continue
            t0_us = line.timestamp_ns / 1e3
            for ev in line.events:
                md = ev_meta[ev.metadata_id]
                tag = None
                for s in _event_strings(plane, ev, md):
                    tag = attribute_op_name(s)
                    if tag:
                        break
                yield ((md.name or "?"), tag,
                       t0_us + ev.offset_ps / 1e6, ev.duration_ps / 1e6,
                       "%s/%s" % (plane.name, line.name))


ASYNC_OVERLAP_ROW = "~async-in-flight (overlapped)"


def _is_async_span(raw_name):
    """True for HLO async-start ops (copy-start/slice-start/
    all-gather-start/...) whose xplane event duration spans the whole
    in-flight window — that window OVERLAPS real compute, so summing it
    with compute rows double-counts wall time (the r05 TPU profile
    read 96% 'other' from exactly this)."""
    head = raw_name.lstrip("%~").split(" ", 1)[0].split(".", 1)[0]
    return head.endswith("-start") or head in ("send", "recv")


def device_op_stats(trace_dir, include_async=False):
    """Aggregate device XLA-op time by Program op from a jax profiler
    trace dir.  Returns {op_type: [calls, total_ms, max_ms, min_ms]};
    events with no pd-tag aggregate under their raw HLO name prefixed
    '~' (so unattributed time stays visible, not silently dropped).
    Async-start spans collapse into the single ``ASYNC_OVERLAP_ROW``
    (their duration overlaps compute rows); ``include_async=True``
    keeps them as individual rows instead."""
    table = {}
    for raw, tag, _ts, dur_us, _line in _iter_device_xla_events(trace_dir):
        # async test FIRST: a tagged async span would otherwise bill
        # its whole overlapped in-flight window to that op's row
        if not include_async and _is_async_span(raw):
            name = ASYNC_OVERLAP_ROW
        elif tag:
            name = tag[0]
        else:
            name = "~" + raw[:60]
        row = table.setdefault(name, [0, 0.0, 0.0, None])
        dt = dur_us / 1e3  # ms
        row[0] += 1
        row[1] += dt
        row[2] = max(row[2], dt)
        row[3] = dt if row[3] is None else min(row[3], dt)
    return table


def device_op_events(trace_dir):
    """Per-event device rows ``[(op_name, ts_us, dur_us, line_name)]``
    with Program-op attribution applied — the chrome-trace material
    (reference ``tools/timeline.py:115`` renders op-named device
    streams); the aggregate view is :func:`device_op_stats`.  Async
    in-flight spans keep their raw HLO name (the timeline SHOWS the
    overlap; attributing them would bill overlapped time to an op)."""
    return [(raw if _is_async_span(raw) else (tag[0] if tag else raw),
             ts, dur, line)
            for raw, tag, ts, dur, line
            in _iter_device_xla_events(trace_dir)]


def _print_device_op_table(table, top=40):
    if not table:
        return
    rows = sorted(table.items(), key=lambda kv: -kv[1][1])[:top]
    name_w = max(len("Op"), *(len(n) for n, _ in rows)) + 2
    print("\n-------------------->  Device per-op Report  "
          "<--------------------\n")
    print("%-*s %-8s %-12s %-12s %-12s %-12s" % (
        name_w, "Op", "Calls", "Total(ms)", "Max(ms)", "Min(ms)",
        "Ave(ms)"))
    for name, (calls, total, mx, mn) in rows:
        print("%-*s %-8d %-12.4f %-12.4f %-12.4f %-12.4f" % (
            name_w, name, calls, total, mx, mn or 0.0, total / calls))
    print()


def stop_profiler(sorted_key=None, profile_path="/tmp/profile"):
    global _enabled, _device_trace
    if not _enabled:
        return
    _enabled = False
    device_events = []
    if _device_trace:
        import jax

        try:
            jax.profiler.stop_trace()
            print("[paddle_tpu.profiler] device trace under %s "
                  "(open with TensorBoard)" % _trace_dir)
        except Exception:
            pass
        _device_trace = False
        # reference-style per-op device table (profiler.h:166), mapped
        # back to Program ops via the executor's pd-scope tags
        try:
            _print_device_op_table(device_op_stats(_trace_dir))
        except Exception as e:  # noqa: BLE001 - table is best-effort
            print("[paddle_tpu.profiler] per-op attribution unavailable: "
                  "%s" % e)
        device_events = _collect_device_events()
    if profile_path:
        try:
            _write_chrome_trace(profile_path,
                                device_events=device_events,
                                spans=_collect_spans())
            print("[paddle_tpu.profiler] %stimeline written to %s "
                  "(open with chrome://tracing)"
                  % ("host+device " if device_events else "host ",
                     profile_path))
        except OSError:
            pass
    _print_summary(sorted_key)


def reset_profiler():
    global _events, _trace_dir
    with _events_lock:
        _events = []
    # a stale dir from a previous session would silently misattribute
    # the next device_op_stats read; a new device trace re-sets it
    _trace_dir = None


@contextlib.contextmanager
def profiler(state="All", sorted_key=None, profile_path="/tmp/profile",
             tracer_option=None):
    start_profiler(state, tracer_option)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


_trace_annotation = None


def trace_annotation():
    """``jax.profiler.TraceAnnotation``, imported once — record_event
    and ``observability.tracing.phase`` sit on the executor's per-step
    path, so they must not pay an ``import jax`` lookup every call."""
    global _trace_annotation
    if _trace_annotation is None:
        import jax

        _trace_annotation = jax.profiler.TraceAnnotation
    return _trace_annotation


def add_host_event(name, t0_us, t1_us):
    """One row of the host-event table (wall-clock epoch microseconds,
    so traces from different hosts merge sensibly in tools/timeline.py);
    dropped unless profiling."""
    if _enabled:
        with _events_lock:
            _events.append((name, threading.get_ident() % 10000,
                            t0_us, t1_us))


@contextlib.contextmanager
def record_event(name):
    """Scoped annotation: host event (when profiling) + device trace
    annotation (reference RecordEvent, profiler.h:81)."""
    # forwarded to the device tracer whether profiling or not, so that
    # annotations show up in externally started jax traces
    t0 = time.time_ns() // 1000
    try:
        with trace_annotation()(name):
            yield
    finally:
        add_host_event(name, t0, time.time_ns() // 1000)


@contextlib.contextmanager
def cuda_profiler(*args, **kwargs):
    # accepted for source compatibility; TPU tracing is the jax profiler
    with profiler():
        yield
