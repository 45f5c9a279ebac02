"""Async dispatch pipeline: lazy fetch handles + device-resident feeds.

The reference overlapped host and device work with PyReader/double-buffer
queues feeding a C++ device worker (``reader.py`` →
``LoDTensorBlockingQueue`` → read op) and served inference through the
async NaiveExecutor loop.  TPU-native, the overlap engine is JAX async
dispatch itself: a jitted call returns *futures* (device arrays whose
computation is still in flight), so the host can stage batch k+1 while
the chip runs batch k — **as long as nothing forces a host sync per
step**.  This module owns the three pieces that keep the loop sync-free:

* :class:`FetchHandle` — a lazy fetch: wraps the un-synced device array a
  step produced and materializes (one device→host sync) only when the
  value is actually read (``np.asarray(h)`` / ``h.numpy()``).
  ``Executor.run(..., return_numpy=False)`` returns these.
* :func:`host_values` / :func:`materialize` — the ONE device→host sync
  point: start every D2H copy asynchronously, then gather, so N fetches
  cost one pipeline-ordered round trip instead of N blocking
  ``np.asarray`` calls.  One ``host.sync`` phase (on a device trace's
  host plane too); with ``fluid.profiler`` on it splits into
  ``executor.device_compute`` (waiting for the in-flight step) +
  ``executor.host_sync`` (the copy).  A handle that a lazy
  ``Executor.run`` returned carries its step, and materialising it is
  where the program learns that the step is done
  (``step_interval_ms``, ``step_latency_ms``).
* :class:`DeviceFeedPipeline` — background-thread prefetch that
  ``jax.device_put``\\ s upcoming feed batches with a configurable depth
  (default 2, env ``PADDLE_TPU_PIPELINE_DEPTH``), so H2D transfer of
  batch k+1 overlaps compute of batch k.  :class:`FeedCache` backs it
  (and the Executor's feed staging): a host array fed repeatedly — a
  constant attention mask, a bench batch — is transferred ONCE and the
  device placement reused.

Everything degrades gracefully on CPU (device_put/copy are host-local),
and exceptions raised on the prefetch thread propagate to the consumer
instead of hanging the queue (the ``buffered`` decorator's contract).
"""

import os
import queue as _queue
import threading
import time

import numpy as np

__all__ = [
    "FetchHandle", "DeviceFeedPipeline", "FeedCache", "host_values",
    "materialize", "detach_device", "device_put_feed",
    "pipeline_depth", "sync_stats", "reset_sync_stats",
]


def pipeline_depth(default=2):
    """Prefetch depth for device feed pipelines: how many upcoming
    batches may be staged on device ahead of the running step
    (``PADDLE_TPU_PIPELINE_DEPTH``, default 2 — classic double
    buffering).  Depth 1 disables lookahead (lowest memory), deeper
    rides out jittery host-side batch assembly."""
    try:
        d = int(os.environ.get("PADDLE_TPU_PIPELINE_DEPTH", "") or default)
    except ValueError:
        d = default
    return max(1, d)


# ---------------------------------------------------------------------------
# the single host-sync point + its accounting
# ---------------------------------------------------------------------------

_sync_lock = threading.Lock()
_sync_count = 0
_sync_wait_ms = 0.0


def sync_stats():
    """{"syncs": N, "sync_wait_ms": total} — every device→host sync this
    process has paid through :func:`host_values` (laziness is testable:
    a fetch handle that was never read leaves the counter alone)."""
    with _sync_lock:
        return {"syncs": _sync_count, "sync_wait_ms": _sync_wait_ms}


def reset_sync_stats():
    global _sync_count, _sync_wait_ms
    with _sync_lock:
        _sync_count = 0
        _sync_wait_ms = 0.0


def _block_all(dev_vals):
    import jax

    jax.block_until_ready(dev_vals)


def host_values(values):
    """Batched device→host conversion with a SINGLE sync point: every
    D2H copy is started asynchronously first, then the results are
    gathered — the per-fetch blocking ``np.asarray`` loop this replaces
    serialized one full dispatch round trip per value.  Accepts a mixed
    list (device arrays, :class:`FetchHandle`, numpy, scalars); returns
    numpy arrays in order.

    When the profiler is on, the wait splits into
    ``executor.device_compute`` (the in-flight step finishing) and
    ``executor.host_sync`` (the copies landing), so dispatch/compute/sync
    overlap is measurable per phase."""
    global _sync_count, _sync_wait_ms

    vals = [v.device_value if isinstance(v, FetchHandle) else v
            for v in values]
    dev = [v for v in vals if hasattr(v, "copy_to_host_async")
           or hasattr(v, "block_until_ready")]
    if not dev:
        return [np.asarray(v) for v in vals]

    from . import profiler as _prof
    from .observability import runtime as _obs
    from .observability import tracing as _tr

    # per executor (its last_done list), the step_info of the newest step
    # among the handles waited for
    done = {}
    for v in values:
        info = getattr(v, "step_info", None)
        if info is not None and not v.synced:
            prev = done.get(id(info[4]))
            if prev is None or info[1] > prev[1]:
                done[id(info[4])] = info
    step = max((info[1] for info in done.values()), default=None)
    with _tr.phase("host.sync", step=step, handles=len(dev)) as sync:
        if _prof.is_profiler_enabled():
            with _prof.record_event("executor.device_compute"):
                _block_all(dev)
            with _prof.record_event("executor.host_sync"):
                out = _copy_all(vals)
        else:
            out = _copy_all(vals)
    for info in done.values():
        _obs.record_step_done(*info)
    with _sync_lock:
        _sync_count += 1
        _sync_wait_ms += sync.dur_ms
    _obs.record_sync(sync.dur_ms, handles=len(dev))
    return out


def _copy_all(vals):
    for v in vals:
        if hasattr(v, "copy_to_host_async"):
            try:
                v.copy_to_host_async()
            except Exception:  # noqa: BLE001 - async copy is best-effort
                pass
    return [np.asarray(v) for v in vals]


class FetchHandle:
    """Lazy fetch: an un-synced device value from an async-dispatched
    step.  Creating (or passing around) a handle costs no host sync; the
    sync happens once, at first materialization (``np.asarray(h)`` /
    ``h.numpy()`` / ``float(h)``) and the host copy is cached.  Batch
    the syncs of many handles with :func:`materialize`.

    ``shape``/``dtype``/``repr`` never sync; ``block_until_ready()``
    waits for the device value without copying it (so
    ``jax.block_until_ready(handles)`` works on pytrees of handles).

    Materializing RELEASES the device buffer (the host copy takes over),
    so a loop that accumulates handles and syncs them in windows holds
    device memory proportional to the un-synced window, not the run.

    ``step_info`` is ``(runner, step, dispatch_ns, drift_key,
    last_done)`` on a handle that a lazy run returned (None otherwise):
    which step this value belongs to, when that step was dispatched
    (``time.perf_counter_ns()``) and its executor's newest completion
    seen, for :func:`host_values`."""

    __slots__ = ("_dev", "_host", "step_info")

    def __init__(self, device_value, step_info=None):
        self._dev = device_value
        self._host = None
        self.step_info = step_info

    @property
    def device_value(self):
        """The raw device array while in flight; after materialization
        the (released) device buffer is replaced by the host copy."""
        return self._host if self._dev is None else self._dev

    @property
    def synced(self):
        """Has this handle already paid its device→host sync?"""
        return self._host is not None

    def numpy(self):
        if self._host is None:
            self._host = host_values([self])[0]
            self._dev = None  # release the device buffer
        return self._host

    def __array__(self, dtype=None):
        a = self.numpy()
        return a.astype(dtype) if dtype is not None else a

    def block_until_ready(self):
        """Wait for the device computation WITHOUT copying to host."""
        if self._host is None and hasattr(self._dev, "block_until_ready"):
            self._dev.block_until_ready()
        return self

    def is_ready(self):
        if self._host is not None:
            return True
        probe = getattr(self._dev, "is_ready", None)
        return bool(probe()) if callable(probe) else True

    @property
    def shape(self):
        return tuple(np.shape(self.device_value))

    @property
    def dtype(self):
        return getattr(self.device_value, "dtype", None)

    def __float__(self):
        return float(self.numpy().reshape(-1)[0])

    def __int__(self):
        return int(self.numpy().reshape(-1)[0])

    def __len__(self):
        s = self.shape
        if not s:
            raise TypeError("len() of a 0-d fetch handle")
        return s[0]

    def __repr__(self):
        return "<FetchHandle shape=%s dtype=%s %s>" % (
            self.shape, self.dtype,
            "synced" if self.synced else "in-flight")


def materialize(fetches):
    """Materialize one handle, or a (possibly nested) list/tuple of
    handles, with ONE batched sync; returns numpy values in the same
    structure.  Non-handle leaves pass through ``np.asarray``."""
    if isinstance(fetches, FetchHandle):
        return fetches.numpy()
    flat = []

    def collect(x):
        if isinstance(x, (list, tuple)):
            for e in x:
                collect(e)
        else:
            flat.append(x)

    collect(fetches)
    need = [h for h in flat
            if isinstance(h, FetchHandle) and not h.synced]
    if need:
        hosts = host_values(need)
        for h, a in zip(need, hosts):
            h._host = a
            h._dev = None  # release the device buffer

    def rebuild(x):
        if isinstance(x, (list, tuple)):
            return type(x)(rebuild(e) for e in x)
        return x.numpy() if isinstance(x, FetchHandle) else np.asarray(x)

    return rebuild(fetches)


def detach_device(value):
    """Device-side copy of a device array WITHOUT a host sync.

    Breaks buffer aliasing between a lazy :class:`FetchHandle` and
    donated scope state: when a fetched value IS a read-write
    persistable, the next in-flight step's ``donate_argnums`` donation
    invalidates that exact buffer, so a handle materialized after the
    next dispatch would read freed memory (the analyzer's
    ``donated-buffer-live-read``).  The copy is dispatched like any
    device op — the step stays async.  Host arrays and non-array
    values pass through untouched."""
    if isinstance(value, np.ndarray) or not hasattr(value, "dtype"):
        return value
    import jax.numpy as jnp

    return jnp.array(value, copy=True)


# ---------------------------------------------------------------------------
# device-resident feeds
# ---------------------------------------------------------------------------


def _cache_enabled():
    return os.environ.get("PADDLE_TPU_FEED_CACHE", "1") != "0"


def _cache_cap(default=64):
    try:
        cap = int(os.environ.get("PADDLE_TPU_FEED_CACHE_CAP", default))
    except ValueError:
        cap = default
    return max(1, cap)


class FeedCache:
    """Bounded LRU placement cache for repeated feeds, keyed by
    ``(name, shape, dtype, content fingerprint)``.

    The original identity-keyed design (same host object per name) never
    hits under serving traffic — every request arrives as a fresh numpy
    array — so constants that recur BY VALUE (an attention-mask bias, a
    shared position-id table) paid one H2D transfer per request.
    Content-shape keying fixes that: a candidate hit (same key) is
    confirmed with an ``is`` identity check (the training-loop fast
    path) or a full ``np.array_equal`` compare (still far cheaper than
    the H2D it saves, and immune to fingerprint collisions — a false
    device-placement reuse would silently corrupt results, so the
    fingerprint only narrows, never decides).  In-place mutation changes
    the fingerprint → new key → miss and re-transfer, same as before.

    The cache is a per-Executor LRU bounded by
    ``PADDLE_TPU_FEED_CACHE_CAP`` (default 64 entries; each predictor —
    i.e. each serving tenant — owns its Executor and therefore its own
    cap); evictions count into ``feed_cache_evictions_total``.  Set
    ``PADDLE_TPU_FEED_CACHE=0`` to disable entirely."""

    def __init__(self, cap=None):
        import collections

        self._entries = collections.OrderedDict()
        self._cap = _cache_cap() if cap is None else max(1, int(cap))
        self._lock = threading.Lock()

    @staticmethod
    def _fingerprint(a):
        n = a.size
        if n == 0:
            return (0,)
        flat = a.reshape(-1)
        sample = flat[:: max(1, n // 64)][:64]
        return sample.tobytes()

    def _key(self, name, a):
        return (name, a.shape, str(a.dtype), self._fingerprint(a))

    def get(self, name, host_value):
        if not _cache_enabled():
            return None
        from .observability import runtime as _obs

        key = self._key(name, host_value)
        with self._lock:
            e = self._entries.get(key)
            if e is not None and (e[0] is host_value
                                  or np.array_equal(e[0], host_value)):
                self._entries.move_to_end(key)
                _obs.record_feed_cache(True)
                return e[1]
        _obs.record_feed_cache(False)
        return None

    def put(self, name, host_value, device_value):
        if not _cache_enabled():
            return
        evicted = 0
        with self._lock:
            key = self._key(name, host_value)
            self._entries[key] = (host_value, device_value)
            self._entries.move_to_end(key)
            while len(self._entries) > self._cap:
                self._entries.popitem(last=False)
                evicted += 1
        if evicted:
            from .observability import runtime as _obs

            _obs.record_feed_cache_eviction(evicted)

    def __len__(self):
        with self._lock:
            return len(self._entries)

    def clear(self):
        with self._lock:
            self._entries.clear()


def _stage(value, name=None, cache=None):
    """One leaf host→device (numpy leaves only; device arrays pass
    through untransferred, non-array python values are left for the
    executor's jnp.asarray)."""
    if not isinstance(value, np.ndarray):
        return value
    if cache is not None and name is not None:
        hit = cache.get(name, value)
        if hit is not None:
            return hit
    import jax

    from .observability import runtime as _obs

    dev = jax.device_put(value)
    _obs.record_feed_h2d(value.nbytes)
    if cache is not None and name is not None:
        cache.put(name, value, dev)
    return dev


def device_put_feed(feed, cache=None):
    """Stage one feed item on device: dict (name→array) feeds cache by
    name; tuple/list items stage each ndarray leaf.  Anything else
    passes through."""
    if isinstance(feed, dict):
        return {n: _stage(v, name=n, cache=cache)
                for n, v in feed.items()}
    if isinstance(feed, (list, tuple)):
        return type(feed)(_stage(v) for v in feed)
    return _stage(feed)


class _PipeEnd:
    pass


class DeviceFeedPipeline:
    """Background prefetch + H2D staging of a feed stream.

    ``source``: an iterable of feed items (dicts/tuples of arrays) or a
    zero-arg callable returning one (a reader creator).  A worker thread
    pulls items and ``jax.device_put``\\ s them into a depth-bounded
    queue, so while step k computes, batch k+1 (and up to ``depth-1``
    more) is already device-resident — the async analogue of the
    reference's double-buffer queue.  Worker exceptions re-raise in the
    consumer; ``stop()`` tears the current epoch down."""

    def __init__(self, source, depth=None, cache=None):
        self._source = source
        self._depth = depth if depth is not None else pipeline_depth()
        self._cache = FeedCache() if cache is None else cache
        self._active = None

    def _spawn(self):
        from .observability import tracing as _tr

        src = self._source() if callable(self._source) else self._source
        q = _queue.Queue(maxsize=max(1, int(self._depth)))
        stop = threading.Event()
        # the prefetch thread's spans join the CONSUMER's trace: capture
        # the spawning thread's context here, attach it inside worker()
        ctx = _tr.capture_context()

        def put(item):
            # never block forever on a full queue: an abandoned consumer
            # (early break, exception mid-loop) sets `stop` and this
            # worker must release its device-staged batches, not leak a
            # thread parked in q.put
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except _queue.Full:
                    continue
            return False

        def worker():
            try:
                with _tr.use_context(ctx):
                    with _tr.span("pipeline.prefetch",
                                  depth=int(self._depth)) as pspan:
                        n = 0
                        for item in src:
                            if stop.is_set():
                                return
                            if not put(device_put_feed(
                                    item, cache=self._cache)):
                                return
                            n += 1
                        pspan.set_attr("items", n)
                    put(_PipeEnd)
            except BaseException as exc:  # propagate, never hang
                put(exc)

        t = threading.Thread(target=worker, daemon=True,
                             name="paddle_tpu-device-feed")
        t.start()
        return q, stop

    def start(self):
        """Begin prefetching ahead of iteration (optional — ``__iter__``
        starts an epoch on demand)."""
        if self._active is None:
            self._active = self._spawn()
        return self

    def stop(self):
        if self._active is not None:
            q, stop = self._active
            stop.set()
            try:
                while True:
                    q.get_nowait()
            except _queue.Empty:
                pass
            self._active = None

    def __iter__(self):
        act = self._active or self._spawn()
        self._active = None
        q, stop = act
        from .observability import runtime as _obs

        try:
            while True:
                # occupancy sampled before the blocking get: qsize==0
                # here means the consumer is about to stall on the
                # producer — the starvation signal the prefetch gauges
                # exist to expose
                _obs.record_prefetch(q.qsize(), q.maxsize)
                item = q.get()
                if item is _PipeEnd:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            stop.set()
            try:  # drop staged batches promptly on early abandonment
                while True:
                    q.get_nowait()
            except _queue.Empty:
                pass
