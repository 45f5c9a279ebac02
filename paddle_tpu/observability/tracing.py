"""Distributed tracing: cross-thread / cross-process spans with
critical-path attribution and a flight recorder for hangs.

The PR-8 telemetry answers "how is the fleet doing"; this module
answers "where did THIS request / THIS step spend its time".  A span is
one timed unit of work (``trace_id``/``span_id``/``parent_id``, wall
start + monotonic duration, attributes, a terminal status).  Spans form
trees within a process, and one *trace* can cross threads (the serving
dispatcher, the :class:`~paddle_tpu.pipeline.DeviceFeedPipeline`
prefetch worker) and processes (a *traceparent* string carried through
worker env, elastic membership records, ``GradExchange`` npz files and
reshard manifests), so a single trace covers
worker-lost→agree→replan→reshard→restore→resume end to end.

Write discipline mirrors :mod:`.journal` exactly: a bounded in-memory
ring of closed spans, buffered JSONL appends into
``PADDLE_TPU_TELEMETRY_DIR`` as ``trace-r<rank>-<pid>.jsonl`` (flushed
every ``PADDLE_TPU_TELEMETRY_FLUSH`` spans; error-status spans flush
immediately), and a torn-line-tolerant reader (:func:`read_traces`).
``PADDLE_TPU_TRACING=0`` is the kill switch: every ``span()`` call
degrades to one cached boolean check returning a shared null stub.

Flight recorder: the tracer always knows the last N closed spans AND
every currently-open span per thread.  :func:`flight_dump` writes that
state as ``flight-r<rank>-<pid>.json`` — the resilience layer calls it
on ``WorkerLostError`` / ``DispatcherCrashedError`` / guard abort, so a
hang postmortem shows which span every thread and rank was inside.

Step phases (:class:`phase`): the two runners time every part of a step
with one helper.  A phase is a ``jax.profiler.TraceAnnotation`` (so it
lies on the host plane of whatever device trace is running, on the
device ops' clock) plus two ``time.perf_counter_ns()`` stamps in a
per-step list.  The stamps become spans only when the step is *kept*:
inside an ambient trace, head-sampled 1 in ``PADDLE_TPU_TRACE_SAMPLE``,
**slow** (over :data:`SLOW_FACTOR` times the running median of its
kind), or **compiled** (it built a block, or jax lowered or compiled
inside it).  jax's own compile events (``jax.monitoring``: trace, lower,
backend compile or persistent-cache retrieval) are closed children of
the phase that paid for them, ``<runner>.compile.<stage>``
(:func:`watch_compiles`).  GC pauses of a millisecond or more are
``host.gc`` spans; the ``gc.callbacks`` hook that notes them takes no
lock and builds no span (it runs wherever a collection fires, inside
this module's own critical sections too): what it noted is recorded
later, outside any lock.

Reconstruct and analyze with ``python -m paddle_tpu.tools.trace DIR``.
"""

import atexit
import gc
import json
import os
import threading
import time
from collections import deque, namedtuple

from .. import profiler as _prof
from . import metrics as _m
from .journal import _rank, journal_dir
from .metrics import _FALSY

__all__ = [
    "SCHEMA_VERSION", "TRACEPARENT_ENV", "SpanContext", "Span",
    "Tracer", "get_tracer", "reset_tracing", "tracing_enabled",
    "set_tracing_enabled", "set_rank", "span", "start_span",
    "phase", "phase_attr", "phase_count", "mark_compiled",
    "watch_compiles", "compile_stages",
    "sample_step", "step_sample_every",
    "current_span",
    "current_context", "current_trace_id", "current_traceparent",
    "capture_context", "use_context", "parse_traceparent",
    "format_traceparent", "inject_env", "remote_parent",
    "set_remote_parent", "flight_dump", "read_traces",
    "read_flight_records", "spans_to_chrome_events",
    "NULL_SPAN",
]

SCHEMA_VERSION = 1

#: env var carrying a W3C-style traceparent into child processes
TRACEPARENT_ENV = "PADDLE_TPU_TRACEPARENT"

_DEFAULT_RING = 1024
_DEFAULT_FLUSH_EVERY = 32

# ---------------------------------------------------------------------------
# kill switch (the metrics.py discipline: lazy env read, cached bool)
# ---------------------------------------------------------------------------

_enabled = None
_enabled_lock = threading.Lock()


def tracing_enabled():
    """True unless ``PADDLE_TPU_TRACING`` is set falsy or
    :func:`set_tracing_enabled` said otherwise."""
    global _enabled
    if _enabled is None:
        with _enabled_lock:
            if _enabled is None:
                _enabled = os.environ.get(
                    "PADDLE_TPU_TRACING", "1").strip().lower() \
                    not in _FALSY
    return _enabled


def set_tracing_enabled(on):
    """Force the kill switch on/off in-process (bench A/B, tests).
    ``None`` re-arms the lazy env read."""
    global _enabled
    with _enabled_lock:
        _enabled = None if on is None else bool(on)


# ---------------------------------------------------------------------------
# ids + traceparent
# ---------------------------------------------------------------------------

SpanContext = namedtuple("SpanContext", ["trace_id", "span_id"])

# span ids: a per-process random prefix + counter is collision-safe
# across processes and ~10x cheaper than urandom per span (span
# creation sits on the executor's per-step hot path)
_id_lock = threading.Lock()
_id_prefix = None
_id_pid = None
_id_counter = 0


def _new_id(nbytes=8):
    if nbytes != 8:
        return os.urandom(nbytes).hex()
    global _id_prefix, _id_pid, _id_counter
    with _id_lock:
        if _id_prefix is None or _id_pid != os.getpid():
            _id_prefix = os.urandom(4).hex()  # fresh after fork too
            _id_pid = os.getpid()
        _id_counter += 1
        n = _id_counter
    return "%s%08x" % (_id_prefix, n & 0xFFFFFFFF)


def new_trace_context():
    """A fresh root context (e.g. a driver minting the trace its child
    processes will all join)."""
    return SpanContext(trace_id=_new_id(16), span_id=_new_id(8))


def format_traceparent(ctx):
    """``00-<trace_id>-<span_id>-01`` (W3C-traceparent shaped)."""
    if ctx is None:
        return None
    return "00-%s-%s-01" % (ctx.trace_id, ctx.span_id)


def parse_traceparent(value):
    """Tolerant parse; returns :class:`SpanContext` or None — a torn or
    foreign header must never break the instrumented path."""
    if not value or not isinstance(value, str):
        return None
    parts = value.strip().split("-")
    if len(parts) < 3:
        return None
    trace_id, span_id = parts[1], parts[2]
    if not trace_id or not span_id:
        return None
    try:
        int(trace_id, 16)
        int(span_id, 16)
    except ValueError:
        return None
    return SpanContext(trace_id=trace_id, span_id=span_id)


# remote parent: the cross-process ambient context this process was
# born with (PADDLE_TPU_TRACEPARENT) or adopted from a peer's record
_remote = {"parsed": False, "ctx": None}
_remote_lock = threading.Lock()


def remote_parent():
    """The ambient cross-process parent context, or None.  Parsed once
    from ``PADDLE_TPU_TRACEPARENT`` unless overridden by
    :func:`set_remote_parent`."""
    if not _remote["parsed"]:
        with _remote_lock:
            if not _remote["parsed"]:
                _remote["ctx"] = parse_traceparent(
                    os.environ.get(TRACEPARENT_ENV))
                _remote["parsed"] = True
    return _remote["ctx"]


def set_remote_parent(value):
    """Adopt a traceparent (string or :class:`SpanContext`) received
    from a peer — e.g. out of a membership record or a reshard
    manifest — as this process's ambient parent.  ``None`` re-arms the
    lazy env read."""
    with _remote_lock:
        if value is None:
            _remote["parsed"] = False
            _remote["ctx"] = None
        else:
            _remote["ctx"] = (value if isinstance(value, SpanContext)
                              else parse_traceparent(value))
            _remote["parsed"] = True


def inject_env(env):
    """Stamp the current traceparent into an env dict for a child
    process (chaos drivers, multiprocess harnesses).  Returns ``env``."""
    tp = current_traceparent()
    if tp:
        env[TRACEPARENT_ENV] = tp
    return env


# ---------------------------------------------------------------------------
# thread-local context stack
# ---------------------------------------------------------------------------

_tls = threading.local()


def _stack():
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def _thread_name():
    name = getattr(_tls, "name", None)
    if name is None:
        name = _tls.name = threading.current_thread().name
    return name


def current_span():
    """Innermost ACTIVE span on this thread (not a bare attached
    context), or None."""
    for entry in reversed(_stack()):
        if isinstance(entry, Span):
            return entry
    return None


def current_context():
    """The context a new span on this thread would parent to: the
    innermost active span or attached context, else the cross-process
    remote parent, else None."""
    stack = _stack()
    if stack:
        top = stack[-1]
        return top.context if isinstance(top, Span) else top
    return remote_parent()


def current_trace_id():
    """Active trace id on this thread (for journal correlation), or
    None."""
    ctx = current_context()
    return ctx.trace_id if ctx is not None else None


def current_traceparent():
    """Formatted traceparent of the current context, or None."""
    return format_traceparent(current_context())


def capture_context():
    """Snapshot the current context for hand-off to another thread
    (pair with :func:`use_context` over there)."""
    return current_context()


class use_context:
    """Attach a captured :class:`SpanContext` on this thread: spans
    started inside parent to it.  ``None`` is a no-op (so call sites
    need no conditional)."""

    __slots__ = ("_ctx", "_pushed")

    def __init__(self, ctx):
        self._ctx = ctx
        self._pushed = False

    def __enter__(self):
        if self._ctx is not None:
            _stack().append(self._ctx)
            self._pushed = True
        return self._ctx

    def __exit__(self, *exc):
        if self._pushed:
            stack = _stack()
            if stack and stack[-1] is self._ctx:
                stack.pop()
            elif self._ctx in stack:
                stack.remove(self._ctx)
        return False


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

class _NullSpan:
    """Shared do-nothing stub returned when tracing is killed — the
    instrumented path pays one cached boolean check and nothing else."""

    __slots__ = ()
    recording = False
    trace_id = span_id = parent_id = None
    context = None
    traceparent = None

    def set_attr(self, key, value):
        return self

    def set_status(self, status):
        return self

    def end(self, status=None):
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class Span:
    """One timed unit of work.  Use as a context manager (activates on
    the current thread) or hold it and call :meth:`end` explicitly — a
    serving request span lives across threads that way."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "attrs",
                 "status", "start_ts", "t0_ns", "dur_ms", "rank", "thread",
                 "_tracer", "_ended", "_active")

    recording = True

    def __init__(self, name, trace_id, parent_id, tracer, attrs=None,
                 start_ts=None, t0_ns=None):
        self.name = str(name)
        self.trace_id = trace_id
        self.span_id = _new_id(8)
        self.parent_id = parent_id
        self.attrs = dict(attrs) if attrs else {}
        self.status = "ok"
        now_ns = time.perf_counter_ns()
        if start_ts is None:
            start_ts = time.time()
        elif t0_ns is None:
            # backdated on the wall clock alone: the same distance back
            # on the monotonic one
            t0_ns = now_ns - int((time.time() - start_ts) * 1e9)
        self.start_ts = start_ts
        self.t0_ns = now_ns if t0_ns is None else t0_ns
        self.dur_ms = None
        self.rank = tracer.rank
        self.thread = _thread_name()
        self._tracer = tracer
        self._ended = False
        self._active = False
        tracer._on_start(self)

    @property
    def context(self):
        return SpanContext(trace_id=self.trace_id, span_id=self.span_id)

    @property
    def traceparent(self):
        return format_traceparent(self.context)

    def set_attr(self, key, value):
        self.attrs[key] = value
        return self

    def set_status(self, status):
        self.status = str(status)
        return self

    def end(self, status=None, dur_ms=None):
        """Close the span (idempotent); duration is monotonic unless
        ``dur_ms`` overrides it (retroactive spans reconstructed from
        measured windows, e.g. device-compute between dispatch and
        sync)."""
        if self._close(status, dur_ms):
            self._tracer._on_end([self])
        return self

    def _close(self, status, dur_ms):
        """Mark ended without telling the tracer; False if it was."""
        if self._ended:
            return False
        self._ended = True
        if status is not None:
            self.status = str(status)
        self.dur_ms = (float(dur_ms) if dur_ms is not None
                       else (time.perf_counter_ns() - self.t0_ns) / 1e6)
        return True

    def to_record(self):
        rec = {"schema": SCHEMA_VERSION, "kind": "span",
               "ts": self.start_ts, "t0_ns": self.t0_ns,
               "rank": self.rank,
               "pid": os.getpid(), "thread": self.thread,
               "trace": self.trace_id, "span": self.span_id,
               "parent": self.parent_id, "name": self.name,
               "dur_ms": (None if self.dur_ms is None
                          else round(self.dur_ms, 4)),
               "status": self.status}
        if self.attrs:
            rec["attrs"] = self.attrs
        return rec

    # context-manager protocol: activate on this thread
    def __enter__(self):
        _stack().append(self)
        self._active = True
        return self

    def __exit__(self, exc_type, exc, tb):
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:
            stack.remove(self)
        self._active = False
        if exc_type is not None and self.status == "ok":
            self.status = "error:%s" % exc_type.__name__
        self.end()
        return False

    def __repr__(self):
        return "Span(%s trace=%s span=%s %s)" % (
            self.name, self.trace_id, self.span_id,
            "open" if not self._ended else "%.3fms" % (self.dur_ms or 0))


def _resolve_parent(parent):
    """Accept a Span, SpanContext, traceparent string, or None."""
    if parent is None:
        return current_context()
    if isinstance(parent, Span):
        return parent.context
    if isinstance(parent, SpanContext):
        return parent
    if isinstance(parent, str):
        return parse_traceparent(parent)
    return None


def start_span(name, parent=None, start_ts=None, t0_ns=None, **attrs):
    """Create a span WITHOUT activating it on this thread (hold it
    across threads; call ``.end()`` when done).  ``parent`` may be a
    Span, :class:`SpanContext` or traceparent string; defaults to the
    current context (new trace root when there is none).  ``start_ts``
    backdates the wall-clock start (retroactive spans) and ``t0_ns``
    the monotonic one (``time.perf_counter_ns()``: the record's
    ``t0_ns``, the clock a caller's loop cuts its windows on)."""
    if not tracing_enabled():
        return NULL_SPAN
    ctx = _resolve_parent(parent)
    if ctx is None:
        trace_id, parent_id = _new_id(16), None
    else:
        trace_id, parent_id = ctx.trace_id, ctx.span_id
    return Span(name, trace_id, parent_id, get_tracer(), attrs=attrs,
                start_ts=start_ts, t0_ns=t0_ns)


def span(name, parent=None, start_ts=None, **attrs):
    """The instrumentation one-liner: ``with tracing.span("x"): ...``.
    Same as :func:`start_span`; returned object is a context manager
    that activates the span on this thread for its body."""
    return start_span(name, parent=parent, start_ts=start_ts, **attrs)


# ---------------------------------------------------------------------------
# step sampling: full fidelity inside a trace, 1-of-N standalone
# ---------------------------------------------------------------------------

_SAMPLE_ENV = "PADDLE_TPU_TRACE_SAMPLE"
_DEFAULT_SAMPLE_EVERY = 16

_sample_every = None


def step_sample_every():
    """``PADDLE_TPU_TRACE_SAMPLE`` (cached): record 1-of-N standalone
    step traces.  1 = every step, 0 = none."""
    global _sample_every
    if _sample_every is None:
        try:
            _sample_every = max(0, int(os.environ.get(
                _SAMPLE_ENV, _DEFAULT_SAMPLE_EVERY)))
        except ValueError:
            _sample_every = _DEFAULT_SAMPLE_EVERY
    return _sample_every


def sample_step(step):
    """Should this step's phase spans record?  A step already inside a
    trace — a serving request, an elastic worker joined via traceparent,
    any enclosing user span — ALWAYS records (those traces are the
    product).  A standalone training loop would mint a fresh root trace
    per step, which is where tracing overhead lives, so it records
    1-of-N (:func:`step_sample_every`) — enough that the trace dir
    still shows representative step-phase breakdowns."""
    if not tracing_enabled():
        return False
    if current_context() is not None:
        return True
    n = step_sample_every()
    if n <= 1:
        return n == 1
    try:
        return int(step) % n == 0
    except (TypeError, ValueError):
        return True


# ---------------------------------------------------------------------------
# step phases: stamps every step, spans for the steps that are kept
# ---------------------------------------------------------------------------

#: a step (or a ``host.sync`` outside any step) whose host time is over
#: this many times the running median of its kind is kept, whatever the
#: head sampling said, with the attribute ``slow=true``
SLOW_FACTOR = 3.0
_MEDIAN_WINDOW = 31     # durations the running median looks back over
_MEDIAN_MIN = 8         # and how many it wants before it calls one slow
# nothing under a millisecond is slow: at that size three times the
# median is the scheduler's noise
_SLOW_FLOOR_NS = 1_000_000
#: a collection shorter than this only counts; a longer one is a span
GC_SPAN_NS = 1_000_000

# entry of a per-step list: name, start and end on perf_counter_ns, the
# parent's index in the list (-1: the root), attrs, status
_NAME, _T0, _T1, _PARENT, _ATTRS, _STATUS = range(6)

# thread ident -> (thread name, that thread's open list), so that the
# flight recorder shows the phase each thread is inside
_open_phases = {}


def _innermost():
    """The innermost open phase's entry on this thread, or None."""
    entries = getattr(_tls, "phases", None)
    return entries[_tls.cur] if entries else None


def phase_attr(key, value):
    """Set an attribute on the innermost open phase of this thread, from
    the place where the fact is known (a cache hit, say), without handing
    the phase down.  Nothing outside a phase."""
    entry = _innermost()
    if entry is not None:
        entry[_ATTRS][key] = value


def phase_count(key, n=1):
    """Add ``n`` to a counting attribute of the innermost open phase."""
    entry = _innermost()
    if entry is not None:
        attrs = entry[_ATTRS]
        attrs[key] = attrs.get(key, 0) + n


class phase:
    """One timed part of a step: ``with phase("executor.feed_stage"):``.

    Always: a ``jax.profiler.TraceAnnotation`` of that name with the
    step's number as its ``step`` (on the host plane of whatever device
    trace is running, whoever started it; outside a session it costs
    well under a microsecond), and ``time.perf_counter_ns()`` at entry
    and exit into this thread's per-step list.  With ``fluid.profiler``
    on, also a row of its host-event table.  Nothing else: no ``Span``,
    no lock.

    The outermost phase on a thread is the root of its list (a runner's
    ``*.step``, or a ``host.sync`` outside any step); phases inside it
    are its descendants and inherit its ``step``.  When the root exits,
    the list becomes ``Span`` records (:func:`start_span` backdated, so
    ids, parents, the ring, JSONL and the flight recorder are the
    ordinary ones) if the step is inside an ambient trace, if
    ``head_sample`` and :func:`sample_step` says so, **if it was
    slow** (:data:`SLOW_FACTOR`; attribute ``slow=true``, not a status),
    **or if it compiled** (:func:`mark_compiled`; ``compiled=true``).
    Otherwise the list is dropped; a GC pause noted inside it is then a
    ``host.gc`` span of its own."""

    __slots__ = ("_entry", "_head_sample", "_ann", "_wall0")

    def __init__(self, name, step=None, head_sample=False, **attrs):
        if step is not None:
            attrs["step"] = step
        self._entry = [name, 0, None, -1, attrs, "ok"]
        self._head_sample = head_sample

    @property
    def t0_ns(self):
        """``time.perf_counter_ns()`` at entry."""
        return self._entry[_T0]

    @property
    def t1_ns(self):
        """``time.perf_counter_ns()`` at exit (None while open)."""
        return self._entry[_T1]

    @property
    def dur_ms(self):
        return (self._entry[_T1] - self._entry[_T0]) / 1e6

    def set_attr(self, key, value):
        self._entry[_ATTRS][key] = value
        return self

    def __enter__(self):
        tls, entry = _tls, self._entry
        entries = getattr(tls, "phases", None)
        if entries is None:
            entries = tls.phases = []
            tls.cur = -1
            tls.stages = tls.cache_source = None
            _open_phases[threading.get_ident()] = (_thread_name(), entries)
        entry[_PARENT] = tls.cur
        tls.cur = len(entries)
        entries.append(entry)
        self._wall0 = time.time_ns() if _prof.is_profiler_enabled() \
            else None
        step = entries[0][_ATTRS].get("step")
        ann = _prof.trace_annotation()
        self._ann = ann(entry[_NAME]) if step is None \
            else ann(entry[_NAME], step=step)
        self._ann.__enter__()
        entry[_T0] = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        entry = self._entry
        entry[_T1] = time.perf_counter_ns()
        self._ann.__exit__(exc_type, exc, tb)
        if self._wall0 is not None:
            _prof.add_host_event(entry[_NAME], self._wall0 // 1000,
                                 time.time_ns() // 1000)
        if exc_type is not None:
            entry[_STATUS] = "error:%s" % exc_type.__name__
        tls = _tls
        tls.cur = entry[_PARENT]
        if entry[_PARENT] < 0:
            entries, tls.phases = tls.phases, None
            _open_phases.pop(threading.get_ident(), None)
            _finish_root(entries, self._head_sample)
        return False


def _is_slow(name, dur_ns):
    """Over :data:`SLOW_FACTOR` times the median of this thread's last
    durations of that root?  Then folds ``dur_ns`` in."""
    windows = getattr(_tls, "medians", None)
    if windows is None:
        windows = _tls.medians = {}
    win = windows.get(name)
    if win is None:
        win = windows[name] = deque(maxlen=_MEDIAN_WINDOW)
    slow = (len(win) >= _MEDIAN_MIN and dur_ns >= _SLOW_FLOOR_NS
            and dur_ns > SLOW_FACTOR * sorted(win)[len(win) // 2])
    win.append(dur_ns)
    return slow


def _finish_root(entries, head_sample):
    """Keep or drop one closed per-step list (the rule is
    :class:`phase`'s).  Runs outside any lock, so it is also where what
    the GC hook noted is recorded."""
    if not tracing_enabled():
        return
    get_tracer()        # from the first step on: the GC hook comes with it
    root = entries[0]
    slow = _is_slow(root[_NAME], root[_T1] - root[_T0])
    if slow:
        root[_ATTRS]["slow"] = True
    keep = (slow or root[_ATTRS].get("compiled")
            or current_context() is not None
            or (head_sample and sample_step(root[_ATTRS].get("step"))))
    for e in entries:
        if e[_NAME] != "host.gc":
            continue
        if keep:        # a child of the phase it interrupted
            _observe_pause(e[_T0], e[_T1], e[_ATTRS]["generation"])
        else:           # the step goes, the pause stays: a span of its own
            _gc_pauses.append((e[_T0], e[_T1], e[_ATTRS], _thread_name()))
    _drain_gc()
    if not keep:
        return
    now_ns, now_ts = time.perf_counter_ns(), time.time()
    # by the entry's place in the list; None for a compile stage that a
    # wider one covered (no entry is a child of a stage)
    spans = []
    for e in entries:
        spans.append(None if e[_NAME] is None else start_span(
            e[_NAME], parent=spans[e[_PARENT]] if e[_PARENT] >= 0 else None,
            start_ts=now_ts - (now_ns - e[_T0]) / 1e9, t0_ns=e[_T0],
            **e[_ATTRS]))
    if not spans[0].recording:      # switched off under our feet
        return
    closed = []
    for s, e in zip(spans, entries):
        if s is not None:
            s._close(e[_STATUS], (e[_T1] - e[_T0]) / 1e6)
            closed.append(s)
    # children before their parents, as live spans would have closed;
    # the whole tree under one lock and in one write
    get_tracer()._on_end(closed[::-1])


# ---------------------------------------------------------------------------
# compile stages: jax's own compile events, children of the paying phase
# ---------------------------------------------------------------------------

#: ``jax.monitoring`` duration event -> stage of a ``<runner>.compile.*``
#: span; a stage's work may run a former stage's (a lowering rule that
#: traces a function), never a later one's
COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_STAGES = tuple(COMPILE_STAGES.values())
# event -> the ``source`` of the next ``backend`` stage on that thread
# (no event, as when the persistent cache is off or skips a small
# module: ``compiled``)
_CACHE_SOURCES = {
    "/jax/compilation_cache/cache_hits": "persistent_cache",
    "/jax/compilation_cache/cache_misses": "compiled",
}
_watch_lock = threading.Lock()
_watching = False


def mark_compiled():
    """The step this thread is inside compiled: it is kept whatever the
    sampling said, with the attribute ``compiled=true``."""
    entries = getattr(_tls, "phases", None)
    if entries:
        entries[0][_ATTRS]["compiled"] = True


def watch_compiles():
    """Register the compile-event listeners, once per process (the
    runners call this before their first compile).  From then on every
    trace, lowering and backend compile jax runs inside a :class:`phase`
    is a closed child of this thread's innermost open phase."""
    global _watching
    if _watching:
        return
    with _watch_lock:
        if _watching:
            return
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(_on_compile_event)
        monitoring.register_event_listener(_on_cache_event)
        _watching = True


def _on_cache_event(event, **kwargs):
    if not tracing_enabled():
        return
    source = _CACHE_SOURCES.get(event)
    if source is not None and getattr(_tls, "phases", None):
        _tls.cache_source = source


def _on_compile_event(event, duration, **kwargs):
    """One of :data:`COMPILE_STAGES` closed just now, ``duration`` seconds
    after it began: a closed ``<runner>.compile.<stage>`` entry (``fun``:
    jax's name of the function; ``source`` on ``backend``) under the
    innermost open phase.  **Nested events count once:** jax reports a
    trace inside a trace, inner first, so an event inside a kept one of
    its stage is dropped, and a kept one that a new event of its stage or
    a later stage covers goes (the traces a lowering rule runs are the
    lowering's time).  A stage's entries therefore never overlap, nor
    does one lie inside a later stage's.  A step that lowered or compiled
    is kept (:func:`mark_compiled`); a trace alone (a function jax traced
    again, whose lowering it had) keeps nothing.  Outside any phase:
    nothing."""
    if not tracing_enabled():
        return
    stage = COMPILE_STAGES.get(event)
    tls = _tls
    entries = getattr(tls, "phases", None)
    if stage is None or not entries:
        return
    t1 = time.perf_counter_ns()
    # jax times the event on its own clock: it began inside this phase
    t0 = max(t1 - int(duration * 1e9), entries[tls.cur][_T0])
    stages = tls.stages
    if stages is None:
        stages = tls.stages = {}
    # kept entries end in order (each was stamped when it closed) and a
    # stage's do not overlap, so those this one covers are the last ones
    for former in _STAGES[:_STAGES.index(stage) + 1]:
        kept = stages.get(former)
        while kept and kept[-1][_T0] >= t0:
            kept.pop()[_NAME] = None
    kept = stages.setdefault(stage, [])
    if kept and kept[-1][_T1] >= t1:
        return
    root = entries[0]
    attrs = {"fun": kwargs.get("fun_name")}
    if stage == "backend":
        attrs["source"] = tls.cache_source or "compiled"
        tls.cache_source = None
    if stage != "trace":
        root[_ATTRS]["compiled"] = True
    entry = ["%s.compile.%s" % (root[_NAME].partition(".")[0], stage),
             t0, t1, tls.cur, attrs, "ok"]
    kept.append(entry)
    entries.append(entry)


def compile_stages(ph):
    """What jax compiled directly under the :class:`phase` ``ph`` of the
    step this thread is inside: ``{"trace_ms", "lower_ms",
    "backend_ms"}``, each stage's union, ``"union_ms"``, the union of
    all three, and ``"source"`` of the last ``backend`` stage.  None when
    nothing was recorded there."""
    stages = getattr(_tls, "stages", None)
    entries = getattr(_tls, "phases", None)
    if not stages or not entries:
        return None
    out = {"trace_ms": 0.0, "lower_ms": 0.0, "backend_ms": 0.0,
           "source": None}
    spans = []
    for stage, kept in stages.items():
        for e in kept:
            if entries[e[_PARENT]] is ph._entry:
                out[stage + "_ms"] += (e[_T1] - e[_T0]) / 1e6
                spans.append((e[_T0], e[_T1]))
                if stage == "backend":
                    out["source"] = e[_ATTRS]["source"]
    if not spans:
        return None
    union, end = 0, None
    for t0, t1 in sorted(spans):
        if end is None or t0 > end:
            union += t1 - t0
            end = t1
        elif t1 > end:
            union += t1 - end
            end = t1
    out["union_ms"] = union / 1e6
    return out


def _open_phase_records(rank, now_ns):
    """What :meth:`Tracer.open_spans` adds for the flight recorder: the
    phases every thread is inside right now."""
    out, now_ts = [], time.time()
    for thread, entries in list(_open_phases.values()):
        for e in list(entries):
            if e[_T1] is None and e[_T0]:
                out.append({
                    "schema": SCHEMA_VERSION, "kind": "span",
                    "ts": now_ts - (now_ns - e[_T0]) / 1e9,
                    "t0_ns": e[_T0], "rank": rank, "pid": os.getpid(),
                    "thread": thread, "trace": None, "span": None,
                    "parent": None, "name": e[_NAME],
                    "dur_ms": round((now_ns - e[_T0]) / 1e6, 4),
                    "status": e[_STATUS], "attrs": dict(e[_ATTRS]),
                    "open": True})
    return out


# ---------------------------------------------------------------------------
# GC pauses: one gc.callbacks hook, installed with the tracer
# ---------------------------------------------------------------------------

# What the hook notes, for :func:`_drain_gc`: collections so far per
# generation, and the pauses of GC_SPAN_NS or more that fell outside any
# phase as (t0_ns, t1_ns, attrs, thread name).  CPython runs one
# collection at a time in a process, callbacks included, so the hook
# needs no lock for them.
_gc_counts = [0, 0, 0]
_gc_pauses = deque()
_gc_folded = [0, 0, 0]  # of _gc_counts, what the counters already hold
_gc_handles = {}        # generation -> (counter, histogram)
_gc_drain_lock = threading.Lock()


def _on_gc(when, info):
    """``gc.callbacks`` hook.  It runs on whichever thread triggered the
    collection, at any bytecode boundary, inside the critical sections of
    this module and of the metrics registry too: so it **takes no lock
    and builds no span**.  It counts the collection and, for a pause of
    :data:`GC_SPAN_NS` or more, notes the two stamps: in the per-step
    list of the phase it interrupted, or outside any phase in
    ``_gc_pauses``.  :func:`_drain_gc` makes counters, observations and
    spans of the notes."""
    if when == "start":
        _tls.gc_t0 = time.perf_counter_ns()
        return
    t1 = time.perf_counter_ns()
    t0 = getattr(_tls, "gc_t0", None)
    if t0 is None:
        return
    _tls.gc_t0 = None
    gen = info.get("generation")
    _gc_counts[gen] += 1
    if t1 - t0 < GC_SPAN_NS:
        return
    attrs = {"generation": gen, "collected": info.get("collected")}
    entries = getattr(_tls, "phases", None)
    if entries:
        entries.append(["host.gc", t0, t1, _tls.cur, attrs, "ok"])
    else:
        # not _thread_name(): threading.current_thread() takes a lock
        # on a thread that Python did not start
        _gc_pauses.append((t0, t1, attrs, getattr(_tls, "name", None)
                           or "thread-%d" % threading.get_ident()))


def _gc_metrics(gen):
    h = _gc_handles.get(gen)
    if h is None:
        h = _gc_handles[gen] = (
            _m.counter("gc_collections_total", generation=str(gen)),
            _m.histogram("gc_pause_ms", generation=str(gen)))
    return h


def _observe_pause(t0, t1, gen):
    if _m.telemetry_enabled():
        _gc_metrics(gen)[1].observe((t1 - t0) / 1e6)


def _drain_gc():
    """Record what :func:`_on_gc` noted: every collection in
    ``gc_collections_total{generation}``; every pause outside a kept
    step in ``gc_pause_ms{generation}`` and as a ``host.gc`` span, a
    trace of its own (a collection is no part of whatever request or
    recovery it fell into; a reader places it by its ``t0_ns``).  Called
    where no lock is held: a root phase's exit, and the tracer's
    ``records()``, ``open_spans()`` and ``flush()`` before they take
    theirs."""
    if not (_gc_pauses or _gc_counts != _gc_folded) \
            or not _gc_drain_lock.acquire(blocking=False):
        return          # nothing noted, or another thread is at it
    try:
        telemetry = _m.telemetry_enabled()
        for gen, total in enumerate(_gc_counts):
            n = total - _gc_folded[gen]
            _gc_folded[gen] = total
            if n and telemetry:
                _gc_metrics(gen)[0].inc(n)
        # what is there now: recording a pause allocates, and a pause
        # noted meanwhile waits for the next drain
        for _ in range(len(_gc_pauses)):
            t0, t1, attrs, thread = _gc_pauses.popleft()
            _observe_pause(t0, t1, attrs["generation"])
            if tracing_enabled() and _tracer is not None:
                s = Span("host.gc", _new_id(16), None, _tracer,
                         attrs=attrs, t0_ns=t0, start_ts=time.time()
                         - (time.perf_counter_ns() - t0) / 1e9)
                s.thread = thread
                s.end(dur_ms=(t1 - t0) / 1e6)
    finally:
        _gc_drain_lock.release()


# ---------------------------------------------------------------------------
# the tracer: ring + JSONL writer + flight recorder (journal discipline)
# ---------------------------------------------------------------------------

class Tracer:
    """One process's closed-span ring + JSONL writer + open-span
    registry.  Thread-safe."""

    def __init__(self, dirname=None, capacity=None, flush_every=None,
                 rank=None):
        self.dirname = dirname
        self.rank = _rank() if rank is None else int(rank)
        if capacity is None:
            try:
                capacity = int(os.environ.get(
                    "PADDLE_TPU_TRACE_RING", _DEFAULT_RING))
            except ValueError:
                capacity = _DEFAULT_RING
        if flush_every is None:
            try:
                flush_every = int(os.environ.get(
                    "PADDLE_TPU_TELEMETRY_FLUSH", _DEFAULT_FLUSH_EVERY))
            except ValueError:
                flush_every = _DEFAULT_FLUSH_EVERY
        self.flush_every = max(int(flush_every), 1)
        self._ring = deque(maxlen=max(int(capacity), 1))
        self._pending = []
        self._open = {}
        self._lock = threading.Lock()
        self._flight_seq = 0
        self._path = None
        if dirname:
            os.makedirs(dirname, exist_ok=True)
            self._path = os.path.join(
                dirname, "trace-r%d-%d.jsonl" % (self.rank, os.getpid()))

    @property
    def path(self):
        return self._path

    def _on_start(self, s):
        with self._lock:
            self._open[s.span_id] = s

    def _on_end(self, spans):
        """Closed spans into the ring and the file: one, or a step's
        whole tree at once (one lock, at most one write)."""
        records = [s.to_record() for s in spans]
        with self._lock:
            for s in spans:
                self._open.pop(s.span_id, None)
            self._ring.extend(records)
            if self._path is not None:
                self._pending.extend(records)
                # error/shed/crash terminals are the spans a dying
                # process must not lose — the journal's URGENT rule
                if (len(self._pending) >= self.flush_every
                        or any(s.status != "ok" for s in spans)):
                    self._flush_locked()

    def records(self):
        """Closed-span ring contents (oldest first)."""
        _drain_gc()
        with self._lock:
            return list(self._ring)

    def open_spans(self):
        """Snapshot of every currently-open span's record (duration =
        time open so far)."""
        _drain_gc()
        now = time.perf_counter_ns()
        with self._lock:
            spans = list(self._open.values())
        out = []
        for s in spans:
            rec = s.to_record()
            rec["open"] = True
            rec["dur_ms"] = round((now - s.t0_ns) / 1e6, 4)
            out.append(rec)
        return out + _open_phase_records(self.rank, now)

    def _flush_locked(self):
        if not self._pending or self._path is None:
            return
        # compact, unsorted: the torn-tolerant reader doesn't care and
        # this encode runs on the span hot path's flush amortization
        lines = "".join(
            json.dumps(r, separators=(",", ":"), default=str) + "\n"
            for r in self._pending)
        self._pending = []
        try:
            with open(self._path, "a") as f:
                f.write(lines)
        except OSError:
            pass  # shared-fs hiccup: the ring still has the spans

    def flush(self):
        _drain_gc()
        with self._lock:
            self._flush_locked()

    def close(self):
        self.flush()

    def flight_record(self, reason):
        """The in-memory postmortem: every open span (what each thread
        is inside RIGHT NOW) plus the last-N closed spans."""
        return {"schema": SCHEMA_VERSION, "kind": "flight",
                "ts": time.time(), "rank": self.rank,
                "pid": os.getpid(), "reason": str(reason)[:500],
                "open_spans": self.open_spans(),
                "recent_spans": self.records()}

    def dump_flight(self, reason, dirname=None):
        """Write the flight record as ``flight-r<rank>-<pid>-<n>.json``
        (atomic tmp+rename); returns the path, or None without a dir."""
        dirname = dirname or self.dirname or journal_dir()
        if not dirname:
            return None
        with self._lock:
            self._flight_seq += 1
            seq = self._flight_seq
            self._flush_locked()
        path = os.path.join(dirname, "flight-r%d-%d-%d.json"
                            % (self.rank, os.getpid(), seq))
        tmp = "%s.tmp.%d" % (path, os.getpid())
        try:
            os.makedirs(dirname, exist_ok=True)
            with open(tmp, "w") as f:
                json.dump(self.flight_record(reason), f, sort_keys=True,
                          default=str)
            os.replace(tmp, path)
        except OSError:
            try:
                os.remove(tmp)
            except OSError:
                pass
            return None
        return path

    def __len__(self):
        return len(self._ring)


_tracer = None
_tracer_lock = threading.Lock()


def get_tracer():
    """The process-wide tracer (created on first use; its directory is
    whatever ``PADDLE_TPU_TELEMETRY_DIR`` said at that moment)."""
    global _tracer
    if _tracer is None:
        with _tracer_lock:
            if _tracer is None:
                t = Tracer(dirname=journal_dir())
                atexit.register(t.close)
                if _on_gc not in gc.callbacks:
                    gc.callbacks.append(_on_gc)
                _tracer = t
    return _tracer


def set_rank(rank):
    """Stamp subsequent spans with this rank.  For launchers that carry
    rank out-of-band (the elastic trainer's ``--rank`` argument) instead
    of the ``PADDLE_TRAINER_ID`` env the tracer reads at creation."""
    get_tracer().rank = int(rank)


def flight_dump(reason, dirname=None):
    """Dump the flight record for a fatal condition (worker lost,
    dispatcher crash, guard abort).  No-op (None) when tracing is
    killed or no tracer exists yet — a postmortem hook must never add a
    second failure."""
    if not tracing_enabled():
        return None
    try:
        return get_tracer().dump_flight(reason, dirname=dirname)
    except Exception:  # noqa: BLE001 - last-resort hook
        return None


def reset_tracing():
    """Drop the singleton + context state so the next span re-reads the
    env (test isolation)."""
    global _tracer
    with _tracer_lock:
        t, _tracer = _tracer, None
    if t is not None:
        t.close()
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)
    _gc_counts[:] = _gc_folded[:] = [0, 0, 0]
    for noted in (_gc_handles, _gc_pauses, _open_phases):
        noted.clear()
    _tls.phases = _tls.medians = _tls.stages = _tls.cache_source = None
    set_tracing_enabled(None)
    set_remote_parent(None)
    global _sample_every
    _sample_every = None
    stack = getattr(_tls, "stack", None)
    if stack:
        del stack[:]


# ---------------------------------------------------------------------------
# readers (torn-line tolerant, the journal discipline)
# ---------------------------------------------------------------------------

def _parse_line(line):
    line = line.strip()
    if not line:
        return None
    try:
        rec = json.loads(line)
    except ValueError:
        return None  # torn trailing write from a killed process
    if not isinstance(rec, dict) or "span" not in rec:
        return None
    try:
        if int(rec.get("schema", 0)) > SCHEMA_VERSION:
            return None  # a future writer; this reader can't vouch
    except (TypeError, ValueError):
        return None
    return rec


def read_traces(path):
    """Parse one ``trace-*.jsonl`` file or every one in a directory,
    merged in timestamp order.  Unparseable lines (torn writes) and
    unknown-schema records are skipped, never raised."""
    paths = []
    if os.path.isdir(path):
        for name in sorted(os.listdir(path)):
            if name.startswith("trace-") and name.endswith(".jsonl"):
                paths.append(os.path.join(path, name))
    elif os.path.exists(path):
        paths.append(path)
    records = []
    for p in paths:
        try:
            with open(p) as f:
                for line in f:
                    rec = _parse_line(line)
                    if rec is not None:
                        records.append(rec)
        except OSError:
            continue
    records.sort(key=lambda r: r.get("ts", 0.0))
    return records


def read_flight_records(path):
    """Every parseable ``flight-*.json`` under a directory (or one
    file), newest first."""
    paths = []
    if os.path.isdir(path):
        for name in sorted(os.listdir(path)):
            if name.startswith("flight-") and name.endswith(".json"):
                paths.append(os.path.join(path, name))
    elif os.path.exists(path):
        paths.append(path)
    out = []
    for p in paths:
        try:
            with open(p) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(rec, dict):
            out.append(rec)
    out.sort(key=lambda r: r.get("ts", 0.0), reverse=True)
    return out


# ---------------------------------------------------------------------------
# chrome-trace conversion (shared by profiler.export_chrome_trace and
# the tools.trace CLI)
# ---------------------------------------------------------------------------

def spans_to_chrome_events(records, flow=True):
    """Convert span records into chrome://tracing events: one ``X``
    (complete) event per closed span on pid ``rank<r>`` / tid = thread
    name, timestamps in wall-clock µs (so per-rank files merge on one
    axis), plus ``s``/``f`` flow arrows for every parent→child edge
    that crosses a thread or process — the causality the flat host and
    device streams can't show."""
    events = []
    by_id = {}
    for r in records:
        sid = r.get("span")
        if sid:
            by_id[sid] = r

    def _pid(r):
        return "rank%s" % r.get("rank", 0)

    pids = set()
    for r in records:
        if r.get("dur_ms") is None or r.get("ts") is None:
            continue
        ts_us = float(r["ts"]) * 1e6
        pid = _pid(r)
        pids.add(pid)
        attrs = dict(r.get("attrs") or {})
        attrs["trace"] = r.get("trace")
        attrs["status"] = r.get("status", "ok")
        events.append({
            "name": r.get("name", "?"), "cat": "span", "ph": "X",
            "pid": pid, "tid": r.get("thread", "main"),
            "ts": ts_us, "dur": max(float(r["dur_ms"]) * 1000.0, 0.1),
            "args": attrs,
        })
        parent = by_id.get(r.get("parent"))
        if (flow and parent is not None
                and parent.get("ts") is not None
                and (parent.get("thread") != r.get("thread")
                     or parent.get("pid") != r.get("pid")
                     or parent.get("rank") != r.get("rank"))):
            fid = "%s/%s" % (r.get("trace"), r.get("span"))
            events.append({
                "name": "span-link", "cat": "span", "ph": "s",
                "id": fid, "pid": _pid(parent),
                "tid": parent.get("thread", "main"),
                "ts": float(parent["ts"]) * 1e6,
            })
            events.append({
                "name": "span-link", "cat": "span", "ph": "f",
                "bp": "e", "id": fid, "pid": pid,
                "tid": r.get("thread", "main"), "ts": ts_us,
            })
    for pid in sorted(pids):
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "args": {"name": "spans:%s" % pid}})
    return events
