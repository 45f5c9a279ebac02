"""Instrumentation hooks — the one-line calls the executor, pipeline,
resilience runtime and fusion resolver make.

Centralizing the metric names and journal kinds here keeps the
instrumented files to single-line edits, and keeps the disabled path
uniform: every hook starts with the cached kill-switch check and
returns immediately when telemetry is off.
"""

import collections
import os
import threading
import time

from . import journal as _journal
from . import metrics as _m
from . import tracing as _tracing
from .metrics import telemetry_enabled

__all__ = [
    "record_step", "record_step_done", "record_jit_cache",
    "record_compile", "record_grad_residual_sites",
    "record_flash_blocks", "record_attention_layers", "record_ssd_chunks",
    "record_grouped_dot_sites",
    "record_moe_layers", "publish_moe_counters",
    "record_fusion_resolve", "record_feed_cache",
    "record_feed_cache_eviction", "record_feed_h2d", "record_sync",
    "record_prefetch", "record_guard_step", "record_guard_skip",
    "record_serving_request", "record_serving_reject",
    "record_serving_shed", "record_serving_batch",
    "record_serving_done", "record_serving_queue_wait",
    "record_serving_sync", "set_serving_depths",
    "set_serving_throughput",
    "record_decode_tokens", "record_decode_request",
    "set_decode_throughput",
    "record_checkpoint_save", "record_checkpoint_load", "record_retry",
    "record_fault", "record_worker_lost", "record_missed_beat",
    "record_concurrency_check", "record_replan", "record_reshard",
    "record_elastic_recovery", "record_join_request",
    "record_join_admitted", "record_warmup", "record_rejoin",
    "set_elastic_state", "record_autoscale_decision",
    "record_decode_resize", "record_dispatcher_died",
    "set_collective_schedule", "collective_step_shape",
    "last_step_info", "reset_runtime",
]


def _trace_id(explicit=None):
    """Trace id to stamp on an urgent journal event: the caller's
    explicit id, else this thread's active trace (which falls back to
    the cross-process ``PADDLE_TPU_TRACEPARENT`` parent) — links the
    monitor's incident sequences to ``tools.trace --id``."""
    if explicit is not None:
        return explicit
    try:
        return _tracing.current_trace_id()
    except Exception:  # noqa: BLE001 - correlation must never raise
        return None

# latest step progress, consumed by the watchdog heartbeat payload so
# `tools/monitor` can tell a wedged-but-alive rank from a healthy one
_last_step = {"step": None, "step_ms": None, "ts": None}
_last_step_lock = threading.Lock()

# per-step collective totals of the last compiled program:
# [(launches_counter, payload_counter, launches, payload_bytes)]
# (counter handles pre-resolved at schedule install, off the step path)
_collective_per_step = []

# hot-path metric handles, resolved once per series: the registry's
# get-or-create pays a sorted-label key build plus a lock per call,
# which is real money at per-step rates.  Populated only while enabled;
# reset_runtime() clears them (reset_telemetry() resets the registry
# too, so a stale handle can never outlive its series).
_step_handles = {}
_jit_handles = {}
_named_handles = {}


def _step_h(runner):
    h = _step_handles.get(runner)
    if h is None:
        h = (_m.counter("steps_total", runner=runner),
             _m.histogram("step_wall_ms", runner=runner),
             _m.histogram("step_enqueue_ms", runner=runner),
             _m.histogram("step_interval_ms", runner=runner),
             _m.histogram("step_latency_ms", runner=runner))
        _step_handles[runner] = h
    return h


def _named(factory, name):
    m = _named_handles.get(name)
    if m is None:
        m = factory(name)
        _named_handles[name] = m
    return m


_env_cache = {}


def _env_int(name, default):
    v = _env_cache.get(name)
    if v is None:
        try:
            v = int(os.environ.get(name, default))
        except ValueError:
            v = default
        _env_cache[name] = v
    return v


def _step_event_every():
    """Journal ``step`` events are SAMPLED (default every 10th step):
    they exist for the monitor's rate/latency view, which step numbers
    make exact anyway, and a per-step JSONL append would be the single
    biggest line item in the <2% overhead budget.  Set
    ``PADDLE_TPU_TELEMETRY_STEP_EVERY=1`` for full per-step fidelity."""
    return max(_env_int("PADDLE_TPU_TELEMETRY_STEP_EVERY", 10), 1)


_snapshot_state = {"steps": 0, "last_write": 0.0, "step_times": 0}


def _maybe_write_snapshot():
    """Refresh ``metrics-r<rank>-<pid>.json`` in the telemetry dir —
    the gauge/histogram side of what the monitor CLI reads (the journal
    carries the events).  Double-throttled: every
    ``PADDLE_TPU_TELEMETRY_SNAPSHOT_EVERY`` steps AND at least
    ``PADDLE_TPU_TELEMETRY_SNAPSHOT_SECS`` apart (the first write is
    exempt so short runs still leave a snapshot)."""
    j = _journal.get_journal()  # its dir is pinned at creation — no
    if j.path is None:          # per-step env read on the hot path
        return
    _snapshot_state["steps"] += 1
    if _snapshot_state["steps"] \
            % max(_env_int("PADDLE_TPU_TELEMETRY_SNAPSHOT_EVERY", 25),
                  1) != 1:
        return
    now = time.time()
    if _snapshot_state["last_write"] and (
            now - _snapshot_state["last_write"]
            < _env_int("PADDLE_TPU_TELEMETRY_SNAPSHOT_SECS", 2)):
        return
    _snapshot_state["last_write"] = now
    from .exporters import write_metrics_snapshot

    write_metrics_snapshot(os.path.join(
        os.path.dirname(j.path),
        "metrics-r%d-%d.json" % (j.rank, os.getpid())))


# ---------------------------------------------------------------------------
# executor / SPMD runner
# ---------------------------------------------------------------------------

def record_step(runner, step, enqueue_ms, wall_ms=None, drift_key=None,
                last_done=None):
    """One dispatched training/inference step.  ``enqueue_ms`` is the
    host's time inside the jitted call (``step_enqueue_ms``).  ``wall_ms``
    is the step's time when the call synced (``return_numpy=True``), and
    None when it returned lazy handles: the enqueue is no step time, so
    the step's time is then observed where a handle is materialised
    (:func:`record_step_done`), or never.  ``last_done`` is the
    executor's ``[step, perf_counter_ns]`` of the newest completion seen:
    step numbers are each executor's own, so the state is too."""
    if not telemetry_enabled():
        return
    steps_c, _, enqueue_h, _, _ = _step_h(runner)
    steps_c.inc()
    enqueue_h.observe(enqueue_ms)
    for launches_c, payload_c, launches, payload in _collective_per_step:
        launches_c.inc(launches)
        payload_c.inc(payload)
    if wall_ms is not None:
        if last_done is not None:
            with _last_step_lock:
                last_done[:] = step, time.perf_counter_ns()
        _observe_step_time(runner, step, wall_ms, drift_key)
    _maybe_write_snapshot()


def record_step_done(runner, step, dispatch_ns, drift_key, last_done):
    """A lazy fetch of ``step`` has reached the host (no sync is added:
    this runs where the caller materialised the handle).  Observes
    ``step_latency_ms`` (dispatch to done) and, when an earlier completion
    of the same executor was observed (``last_done``, as in
    :func:`record_step`), ``step_interval_ms``: the time since it, per
    step advanced.  The interval is the loop's true step time and feeds
    what ``wall_ms`` feeds in a synced loop."""
    if not telemetry_enabled():
        return
    now = time.perf_counter_ns()
    _, _, _, interval_h, latency_h = _step_h(runner)
    latency_h.observe((now - dispatch_ns) / 1e6)
    with _last_step_lock:
        prev_step, prev_ns = last_done
        if prev_step is not None and step <= prev_step:
            return      # a second handle of a step already seen done
        last_done[:] = step, now
    if prev_step is not None:
        interval_ms = (now - prev_ns) / 1e6 / (step - prev_step)
        interval_h.observe(interval_ms)
        _observe_step_time(runner, step, interval_ms, drift_key)


def _observe_step_time(runner, step, step_ms, drift_key):
    """What every reader of a step's time is fed: ``step_wall_ms``, the
    watchdog's ``last_step_info``, the journal's sampled ``step`` event
    and the drift monitor."""
    _step_h(runner)[1].observe(step_ms)
    with _last_step_lock:
        _last_step["step"] = step
        _last_step["step_ms"] = step_ms
        _last_step["ts"] = time.time()
        _snapshot_state["step_times"] += 1
        seen = _snapshot_state["step_times"]
    ev = _step_event_every()
    if ev == 1 or seen % ev == 1:
        _journal.emit("step", runner=runner, step=step,
                      wall_ms=round(step_ms, 4))
    if drift_key is not None:
        from . import drift as _drift

        _drift.monitor().observe_step(step_ms, key=drift_key, step=step)


def record_jit_cache(hit, runner="executor", cause=None):
    """A runner's lookup; a miss counts under its ``cause``
    (``executor._miss_cause``)."""
    _tracing.phase_attr("hit", bool(hit))
    if not telemetry_enabled():
        return
    key = (runner, bool(hit), cause)
    c = _jit_handles.get(key)
    if c is None:
        c = _m.counter("jit_cache_hits_total", runner=runner) if hit \
            else _m.counter("jit_cache_misses_total", runner=runner,
                            cause=str(cause))
        _jit_handles[key] = c
    c.inc()


def record_compile(runner, step, cause, build_ms, stages):
    """A compile has returned: a block's first dispatch (``build_ms`` its
    ``compile`` phase), or a dispatch whose lookup hit and which jax
    compiled again (``cause`` ``arguments``, ``build_ms`` 0).  ``stages``
    are ``tracing.compile_stages`` of that ``dispatch`` phase (None where
    nothing was recorded, as under the kill switch): ``compile_ms{runner}``
    observes the build plus the union of the stages, and the journal's
    ``compile`` event carries the step, the cause and each stage."""
    if not telemetry_enabled():
        return
    stages = stages or {}
    ms = build_ms + stages.get("union_ms", 0.0)
    _m.histogram("compile_ms", runner=runner).observe(ms)
    _journal.emit(
        "compile", runner=runner, step=step, cause=cause,
        compile_ms=round(ms, 2),
        **{k: round(stages.get(k, 0.0), 2)
           for k in ("trace_ms", "lower_ms", "backend_ms")},
        source=stages.get("source"))


def record_grad_residual_sites(sites, compile_phase):
    """What the backward of each Mosaic kernel site of a newly traced
    block took, ``(op_type, path, kept_bytes)`` each: the forward op's
    saved residuals (``reused``), what the site's forward kernel gave,
    kept across its recompute region (``kept_across_region``, and the
    bytes so kept), or a second run of the forward kernel
    (``recomputed``; ``executor._run_ops_into_env``).  The three counts
    and ``recompute_kept_bytes`` are attributes of the block's
    ``compile`` phase."""
    sites = list(sites)
    counts = collections.Counter((t, p) for t, p, _ in sites)
    for path in ("reused", "recomputed", "kept_across_region"):
        compile_phase.set_attr(
            "grad_residual_sites_" + path,
            sum(n for (_, p), n in counts.items() if p == path))
    compile_phase.set_attr("recompute_kept_bytes",
                           sum(b for _, _, b in sites))
    if not telemetry_enabled():
        return
    for (op_type, path), n in counts.items():
        _m.counter("grad_residual_sites_total",
                   "kernel sites whose backward reused the forward op's "
                   "residuals, took what was kept across a recompute "
                   "region, or ran the forward kernel again",
                   op_type=op_type, path=path).inc(n)


def record_flash_blocks(blocks, compile_phase):
    """The grid blocks of the flash kernels a newly traced step holds,
    ``blocks[(kernel, kind)]`` as ``flash_attention.noting_blocks``
    counted them (kernel ``fwd`` | ``dkv`` | ``dq``; kind ``possible``:
    the whole rectangle, ``visited``: those a sweep computes, ``masked``:
    visited blocks the causal diagonal or a window's edge crosses; the
    same three again as ``window_<kind>`` for the sites with a window),
    summed over the sites: attributes ``flash_blocks_<kind>`` of the
    block's ``compile`` phase, and ``flash_window_blocks_<kind>`` where a
    site has a window; a step without a flash kernel gets none."""
    if not blocks:
        return
    totals = collections.Counter()
    for (_, kind), n in blocks.items():
        totals["flash_window_blocks_" + kind[len("window_"):]
               if kind.startswith("window_") else "flash_blocks_" + kind] += n
    for name, n in sorted(totals.items()):
        compile_phase.set_attr(name, n)
    if not telemetry_enabled():
        return
    for (kernel, kind), n in blocks.items():
        if kind.startswith("window_"):
            continue
        windowed = blocks.get((kernel, "window_" + kind), 0)
        for window, n in (("false", n - windowed), ("true", windowed)):
            if n or window == "false":
                _m.counter("flash_blocks_total",
                           "grid blocks of the flash kernels in traced "
                           "steps: possible, visited, and masked among the "
                           "visited; window: of a site with a window",
                           kernel=kernel, kind=kind, window=window).inc(n)


def record_ssd_chunks(chunks, compile_phase):
    """The scan kernels' chunks (times batch and heads) a newly traced
    training step holds, ``chunks[pass]`` as ``ssd_scan.noting_chunks``
    counted them (``forward``; ``region_rerun``: a recompute region's
    re-run ran the forward kernel again; ``backward``): attributes
    ``ssd_chunks_<pass>`` of the block's ``compile`` phase and the
    counter ``ssd_chunks_total{pass}``.  A step without a backward pass
    (a test-mode forward) is not a training step and is left out."""
    if not chunks.get("backward"):
        return
    for kind, n in sorted(chunks.items()):
        compile_phase.set_attr("ssd_chunks_" + kind, n)
    if not telemetry_enabled():
        return
    for kind, n in chunks.items():
        _m.counter("ssd_chunks_total",
                   "chunks of the Mamba-2 scan kernels in traced training "
                   "steps, times batch and heads, by pass: forward, a "
                   "recompute region's re-run, backward",
                   **{"pass": kind}).inc(n)


def record_grouped_dot_sites(sites, compile_phase):
    """The grouped products a newly traced step holds, ``sites[(mode,
    path, shape, tiles)]`` as ``grouped_matmul.noting_sites`` counted them
    (mode ``forward``, ``lhs_grad``, ``rhs_grad``; path ``kernel`` or
    ``xla``): attributes ``grouped_dot_sites_<mode>_<path>`` and
    ``grouped_dot_tiles`` (``<mode> <m>x<k>x<n>: <tm>x<tk>x<tn>`` for each
    kernel's shape) of the block's ``compile`` phase, and the counter
    ``grouped_dot_sites_total{mode, path}``; a step without one gets
    none."""
    if not sites:
        return
    counts, tiles = collections.Counter(), set()
    for (mode, path, shape, tiling), n in sites.items():
        counts[(mode, path)] += n
        if tiling:
            tiles.add("%s %s: %s" % (mode, shape, tiling))
    for (mode, path), n in sorted(counts.items()):
        compile_phase.set_attr("grouped_dot_sites_%s_%s" % (mode, path), n)
    if tiles:
        compile_phase.set_attr("grouped_dot_tiles", "; ".join(sorted(tiles)))
    if not telemetry_enabled():
        return
    for (mode, path), n in counts.items():
        _m.counter("grouped_dot_sites_total",
                   "grouped products in traced steps, by mode (forward, "
                   "lhs_grad, rhs_grad) and path (the Pallas kernel or "
                   "XLA's ragged_dot)", mode=mode, path=path).inc(n)


def record_attention_layers(program, compile_phase):
    """The fused attention sites of a newly compiled block, as attributes
    of its ``compile`` phase: ``attention_layers_sliding`` (sites with a
    window), ``attention_layers_full`` and ``kv_heads`` (of the first
    site whose K has fewer heads than its Q, else of the first); a block
    without a site gets none."""
    sites = [op for b in getattr(program, "blocks", ()) for op in b.ops
             if op.type == "fused_multihead_attention"]
    if not sites:
        return
    sliding = sum(1 for op in sites if op.attrs.get("window"))
    compile_phase.set_attr("attention_layers_sliding", sliding)
    compile_phase.set_attr("attention_layers_full", len(sites) - sliding)
    heads = [(op.block._find_var_recursive(op.input("Q")[0]).shape[1],
              op.block._find_var_recursive(op.input("K")[0]).shape[1])
             for op in sites]
    compile_phase.set_attr(
        "kv_heads", int(next((kv for h, kv in heads if kv != h),
                             heads[0][1])))


# stats var of each expert layer a compiled step holds -> its layer
# label, and what ``publish_moe_counters`` last read and has summed
_moe_stats = {}
_moe_read = {}


def record_moe_layers(program, compile_phase):
    """The expert layers of a newly compiled block, as attributes of its
    ``compile`` phase (``moe_layers``, ``experts_held``,
    ``experts_total``; a block without any gets none), and the counters
    their ``moe_count_rows`` ops keep on the device, noted for
    :func:`publish_moe_counters`."""
    ops = [op for b in getattr(program, "blocks", ()) for op in b.ops]
    experts = [op for op in ops if op.type == "moe_experts"]
    if not experts:
        return
    compile_phase.set_attr("moe_layers", len(experts))
    compile_phase.set_attr("experts_held", len(experts[0].input("WDown")))
    for op in ops:
        if op.type == "moe_route":
            weight = op.block._find_var_recursive(op.input("Weight")[0])
            compile_phase.set_attr("experts_total", int(weight.shape[1]))
        elif op.type == "moe_count_rows":
            _moe_stats[op.input("Stats")[0]] = str(op.attrs.get("layer"))


def publish_moe_counters(scope=None):
    """Reads each noted expert layer's device counters (one host sync a
    layer; nothing is read until this is called) and publishes what was
    added since the last read: counters ``moe_rows_routed_here_total``,
    ``moe_rows_possible_total`` (tokens * top_k) and
    ``moe_buffer_rows_moved_total`` (the rows dispatch gathered and
    combine added back: a whole block for each block the layer's loop
    ran), gauges ``moe_expert_rows_max`` and ``moe_expert_rows_mean``
    over the held experts' totals, each with ``layer``.  Returns
    ``{layer: {"rows": [per held expert], "possible": n, "moved": n,
    "steps": n}}``, the totals since the counters were last zeroed;
    ``moved / possible`` is the share of the choices' order that was
    touched (1: the loop never stopped early)."""
    import numpy as np

    if scope is None:
        from ..executor import global_scope
        scope = global_scope()
    out = {}
    for name, layer in sorted(_moe_stats.items()):
        value = scope.get(name)
        if value is None:
            continue
        raw = np.asarray(value).astype(np.int64) & 0xFFFFFFFF
        last, total = _moe_read.get(name, (np.zeros_like(raw),) * 2)
        # the device's int32 wraps; a step count that fell, or a vector of
        # another length (another program's layer), is a restart
        restart = last.shape != raw.shape or raw[-1] < last[-1]
        delta = raw if restart else (raw - last) & 0xFFFFFFFF
        total = delta + (0 if restart else total)
        _moe_read[name] = (raw, total)
        rows = [int(x) for x in total[:-3]]
        out[layer] = {"rows": rows, "possible": int(total[-3]),
                      "moved": int(total[-2]), "steps": int(total[-1])}
        if telemetry_enabled():
            _m.counter("moe_rows_routed_here_total",
                       "rows the experts held here were given",
                       layer=layer).inc(int(delta[:-3].sum()))
            _m.counter("moe_rows_possible_total",
                       "tokens * top_k: the rows all experts were given",
                       layer=layer).inc(int(delta[-3]))
            _m.counter("moe_buffer_rows_moved_total",
                       "rows dispatch gathered and combine added back",
                       layer=layer).inc(int(delta[-2]))
            _m.gauge("moe_expert_rows_max", layer=layer).set(max(rows))
            _m.gauge("moe_expert_rows_mean",
                     layer=layer).set(sum(rows) / len(rows))
    return out


def record_fusion_resolve(hit):
    _tracing.phase_attr("hit", bool(hit))
    if not telemetry_enabled():
        return
    _named(_m.counter,
           "fusion_resolve_cache_hits_total" if hit
           else "fusion_resolve_cache_misses_total").inc()


# ---------------------------------------------------------------------------
# async pipeline
# ---------------------------------------------------------------------------

def record_feed_cache(hit):
    _tracing.phase_count("hits" if hit else "misses")
    if not telemetry_enabled():
        return
    _named(_m.counter,
           "feed_cache_hits_total" if hit
           else "feed_cache_misses_total").inc()


def record_feed_h2d(nbytes):
    """One host array copied to the device (a feed-cache miss, or a feed
    staged with no cache)."""
    _tracing.phase_count("bytes", nbytes)
    if not telemetry_enabled():
        return
    _named(_m.counter, "feed_h2d_bytes_total").inc(nbytes)


def record_feed_cache_eviction(n=1):
    """LRU eviction(s) from the bounded feed placement cache."""
    if not telemetry_enabled():
        return
    _named(_m.counter, "feed_cache_evictions_total").inc(n)


def record_sync(wait_ms, handles=1):
    """One batched device->host sync drained ``handles`` handles."""
    if not telemetry_enabled():
        return
    _named(_m.counter, "host_syncs_total").inc()
    _named(_m.counter, "host_sync_handles_total").inc(handles)
    _named(_m.histogram, "host_sync_wait_ms").observe(wait_ms)


def record_prefetch(depth, capacity):
    """Prefetch queue occupancy observed at a consumer get()."""
    if not telemetry_enabled():
        return
    _named(_m.counter, "prefetch_gets_total").inc()
    _named(_m.gauge, "prefetch_queue_depth").set(depth)
    if capacity:
        _named(_m.gauge, "prefetch_occupancy").set(
            depth / float(capacity))


# ---------------------------------------------------------------------------
# serving (paddle_tpu/serving — the continuous-batching server)
# ---------------------------------------------------------------------------

def record_serving_request(tenant):
    if not telemetry_enabled():
        return
    _m.counter("serving_requests_total", tenant=tenant).inc()


def record_serving_reject():
    """Backpressure rejection (bounded queue full)."""
    if not telemetry_enabled():
        return
    _named(_m.counter, "serving_rejected_total").inc()


def record_serving_shed(tenant):
    """SLA priority eviction: a request shed before dispatch."""
    if not telemetry_enabled():
        return
    _m.counter("serving_shed_total", tenant=tenant).inc()
    _journal.emit("request-shed", tenant=tenant)


def record_serving_batch(tenant, bucket, rows):
    """One coalesced batch dispatched: occupancy = real rows over the
    padded bucket size (1.0 means no padding waste)."""
    if not telemetry_enabled():
        return
    _m.counter("serving_batches_total", tenant=tenant).inc()
    _named(_m.counter, "serving_rows_total").inc(rows)
    _named(_m.counter, "serving_padded_rows_total").inc(bucket - rows)
    _named(_m.gauge, "serving_batch_occupancy").set(
        rows / float(bucket) if bucket else 0.0)


def record_serving_done(tenant, latency_ms):
    """One request completed (enqueue→result latency)."""
    if not telemetry_enabled():
        return
    _m.counter("serving_completed_total", tenant=tenant).inc()
    _named(_m.histogram, "serving_latency_ms").observe(latency_ms)


def record_serving_queue_wait(tenant, wait_ms):
    """Enqueue→batch-formation wait of one request (the queue_wait
    span's interval) — the histogram shedding decisions are diagnosed
    from."""
    if not telemetry_enabled():
        return
    _named(_m.histogram, "serving_queue_wait_ms").observe(wait_ms)


def record_serving_sync(tenant, sync_ms):
    """One batched materialize (the serving.sync span's interval)."""
    if not telemetry_enabled():
        return
    _named(_m.histogram, "serving_sync_ms").observe(sync_ms)


def set_serving_depths(queued, inflight):
    if not telemetry_enabled():
        return
    _named(_m.gauge, "serving_queue_depth").set(queued)
    _named(_m.gauge, "serving_inflight_depth").set(inflight)


def set_serving_throughput(qps):
    if not telemetry_enabled():
        return
    _named(_m.gauge, "serving_throughput_qps").set(qps)


def record_decode_tokens(tenant, n):
    """``n`` tokens generated this decode step across a tenant's active
    slots (the autoregressive analogue of serving_rows_total)."""
    if not telemetry_enabled():
        return
    _m.counter("serving_decode_tokens_total", tenant=tenant).inc(n)


def record_decode_request(tenant, generated_len, ttft_ms=None):
    """One generation request finished: its generated length (the
    per-request histogram capacity planning reads) and, when known, its
    time-to-first-token."""
    if not telemetry_enabled():
        return
    _named(_m.histogram, "serving_generated_len").observe(generated_len)
    if ttft_ms is not None:
        _named(_m.histogram, "serving_ttft_ms").observe(ttft_ms)


def set_decode_throughput(tokens_per_sec):
    if not telemetry_enabled():
        return
    _named(_m.gauge, "decode_tokens_per_sec").set(tokens_per_sec)


def set_kv_pool(tenant, total, free):
    """Paged-KV pool state after an allocate/free: the capacity-
    planning gauges ``tools.monitor`` renders, plus the occupancy
    ratio the ``--alert 'kv_pool_occupancy>0.9'`` predicate watches
    (high occupancy means admissions are about to backpressure)."""
    if not telemetry_enabled():
        return
    _m.gauge("kv_blocks_total", tenant=tenant).set(total)
    _m.gauge("kv_blocks_free", tenant=tenant).set(free)
    occ = 1.0 - free / float(total) if total else 0.0
    _m.gauge("kv_pool_occupancy", tenant=tenant).set(occ)


def record_kv_handoff(tenant, wait_ms, blocks):
    """One prefill->decode KV-block handoff (disaggregated serving):
    how long the finished prefill waited for a decode slot, and how
    many pool blocks changed owner without a copy."""
    if not telemetry_enabled():
        return
    _m.counter("serving_kv_handoffs_total", tenant=tenant).inc()
    _m.counter("serving_kv_handoff_blocks_total",
               tenant=tenant).inc(blocks)
    _named(_m.histogram, "serving_kv_handoff_wait_ms").observe(wait_ms)


def record_spec_round(tenant, proposed, accepted):
    """One speculative-decoding verify round: ``proposed`` draft
    tokens checked, ``accepted`` of them kept (the bonus token is not
    counted on either side).  The cumulative ratio feeds the
    ``spec_acceptance_rate`` gauge bench gates on."""
    if not telemetry_enabled():
        return
    p = _m.counter("spec_tokens_proposed_total", tenant=tenant)
    a = _m.counter("spec_tokens_accepted_total", tenant=tenant)
    p.inc(proposed)
    a.inc(accepted)
    if p.value:
        _m.gauge("spec_acceptance_rate",
                 tenant=tenant).set(a.value / float(p.value))


# ---------------------------------------------------------------------------
# resilience runtime
# ---------------------------------------------------------------------------

def record_guard_step(finite):
    if not telemetry_enabled():
        return
    _named(_m.counter, "guard_steps_total").inc()
    if not finite:
        _named(_m.counter, "guard_skips_total").inc()


def record_guard_skip(step, consecutive):
    if not telemetry_enabled():
        return
    _journal.emit("guard-skip", step=step, consecutive=consecutive)


def record_checkpoint_save(step, duration_ms, nbytes, path):
    if not telemetry_enabled():
        return
    _m.counter("checkpoint_saves_total").inc()
    _m.histogram("checkpoint_save_ms").observe(duration_ms)
    _m.counter("checkpoint_bytes_written_total").inc(nbytes)
    _m.gauge("checkpoint_last_step").set(step if step is not None else -1)
    _m.gauge("checkpoint_last_save_ts").set(time.time())
    _journal.emit("checkpoint-saved", step=step,
                  duration_ms=round(duration_ms, 2), bytes=nbytes,
                  path=os.path.basename(str(path)))


def record_checkpoint_load(step, duration_ms, path):
    if not telemetry_enabled():
        return
    _m.counter("checkpoint_loads_total").inc()
    _m.histogram("checkpoint_load_ms").observe(duration_ms)
    _journal.emit("checkpoint-loaded", step=step,
                  duration_ms=round(duration_ms, 2),
                  path=os.path.basename(str(path)))


def record_retry(site):
    if not telemetry_enabled():
        return
    _m.counter("retries_total", site=site or "unknown").inc()


def record_fault(kind, step=None, site=None):
    if not telemetry_enabled():
        return
    _m.counter("faults_injected_total", kind=kind).inc()
    _journal.emit("fault-injected", fault=kind, step=step, site=site)


def record_worker_lost(ranks, reason="", trace=None):
    if not telemetry_enabled():
        return
    _m.counter("workers_lost_total").inc(max(len(ranks), 1))
    _journal.emit("worker-lost", ranks=list(ranks), reason=reason,
                  trace=_trace_id(trace))
    _tracing.flight_dump("worker-lost: ranks=%s %s" % (list(ranks),
                                                       reason))


def record_replan(epoch, old_world, new_world, plan, duration_ms):
    """One elastic re-plan: the survivors re-transpiled for the shrunk
    world and the new schedule passed the deadlock/race provers."""
    if not telemetry_enabled():
        return
    _named(_m.counter, "elastic_replans_total").inc()
    _named(_m.histogram, "elastic_replan_ms").observe(duration_ms)
    _journal.emit("replan", epoch=epoch, old_world=old_world,
                  new_world=new_world, plan=str(plan),
                  duration_ms=round(duration_ms, 2), trace=_trace_id())


def record_reshard(step, old_world, new_world, vars_resharded,
                   duration_ms, path):
    """One checkpoint reshard old→new topology (resilience.reshard)."""
    if not telemetry_enabled():
        return
    _named(_m.counter, "reshards_total").inc()
    _named(_m.histogram, "reshard_ms").observe(duration_ms)
    _journal.emit("reshard", step=step, old_world=old_world,
                  new_world=new_world, vars=vars_resharded,
                  duration_ms=round(duration_ms, 2),
                  path=os.path.basename(str(path)), trace=_trace_id())


def record_elastic_recovery(epoch, step, new_world, recovery_ms):
    """End of one elastic recovery: detect→first post-resume step,
    completed in-process (no restart).  Closes the incident chain the
    monitor renders (worker-lost → replan → reshard → resume)."""
    if not telemetry_enabled():
        return
    _named(_m.counter, "elastic_recoveries_total").inc()
    _named(_m.histogram, "elastic_recovery_ms").observe(recovery_ms)
    _m.gauge("elastic_world_size").set(new_world)
    _journal.emit("resume", epoch=epoch, step=step, world=new_world,
                  recovery_ms=round(recovery_ms, 2), trace=_trace_id())


def record_join_request(rank, epoch):
    """A returning/new worker posted its write-once join request and is
    heartbeating for admission (resilience.elastic scale-up)."""
    if not telemetry_enabled():
        return
    _named(_m.counter, "elastic_join_requests_total").inc()
    _journal.emit("join-request", rank=int(rank), epoch=int(epoch),
                  trace=_trace_id())


def record_join_admitted(epoch, joiners, writer=None):
    """The epoch writer admitted pending joiners into the next epoch's
    warm-up round."""
    if not telemetry_enabled():
        return
    _named(_m.counter, "elastic_admissions_total").inc()
    _journal.emit("admitted", epoch=int(epoch),
                  joiners=[int(r) for r in joiners],
                  writer=writer, trace=_trace_id())


def record_warmup(rank, epoch, warmup_ms):
    """An admitted joiner finished compiling + dry-running its worker
    program and acked ready — the fleet stepped at the old epoch the
    whole time."""
    if not telemetry_enabled():
        return
    _named(_m.histogram, "elastic_warmup_ms").observe(warmup_ms)
    _journal.emit("warmup", rank=int(rank), epoch=int(epoch),
                  warmup_ms=round(warmup_ms, 2), trace=_trace_id())


def record_rejoin(epoch, step, new_world, rejoin_ms):
    """A joiner completed its first full-world step: join-request →
    admitted → warm-up → replan/reshard → stepping, measured end to
    end."""
    if not telemetry_enabled():
        return
    _named(_m.counter, "elastic_rejoins_total").inc()
    _named(_m.histogram, "elastic_rejoin_ms").observe(rejoin_ms)
    _m.gauge("elastic_world_size").set(new_world)
    _journal.emit("resume", epoch=epoch, step=step, world=new_world,
                  rejoin_ms=round(rejoin_ms, 2), trace=_trace_id())


def set_elastic_state(epoch, world, pending=None):
    """Current membership as gauges (monitor surfaces these):
    membership epoch, world size, and — when known — the number of
    joiners pending admission/warm-up."""
    if not telemetry_enabled():
        return
    _m.gauge("membership_epoch").set(int(epoch))
    _m.gauge("elastic_world_size").set(int(world))
    if pending is not None:
        _m.gauge("elastic_pending_joins").set(int(pending))


def record_autoscale_decision(action, reason, world=None,
                              target_world=None, evidence=None):
    """One autoscaler control-loop verdict, journaled with the evidence
    it was decided on (resilience.autoscale)."""
    if not telemetry_enabled():
        return
    _m.counter("autoscale_decisions_total", action=str(action)).inc()
    _journal.emit("autoscale", action=str(action),
                  reason=str(reason)[:300], world=world,
                  target_world=target_world,
                  evidence=dict(evidence or {}), trace=_trace_id())


def record_decode_resize(tenant, old_slots, new_slots):
    """A DecodeEngine drained and rebuilt its KV-cache slots at a new
    count (autoscaler serving surface)."""
    if not telemetry_enabled():
        return
    _named(_m.counter, "decode_resizes_total").inc()
    _m.gauge("decode_slots", tenant=str(tenant)).set(int(new_slots))
    _journal.emit("autoscale", action="resize-slots",
                  reason="decode tenant %s: %d -> %d slots"
                         % (tenant, old_slots, new_slots),
                  world=None, target_world=None,
                  evidence={"tenant": str(tenant),
                            "old_slots": int(old_slots),
                            "new_slots": int(new_slots)},
                  trace=_trace_id())


def record_dispatcher_died(reason, failed_requests, trace=None):
    """The serving dispatcher thread crashed: every pending request was
    failed with a typed error instead of stranding callers."""
    if not telemetry_enabled():
        return
    _named(_m.counter, "serving_dispatcher_crashes_total").inc()
    _journal.emit("dispatcher-died", reason=str(reason)[:200],
                  failed_requests=int(failed_requests),
                  trace=_trace_id(trace))
    _tracing.flight_dump("dispatcher-died: %s" % str(reason)[:200])


def record_missed_beat(ranks):
    if not telemetry_enabled():
        return
    _m.counter("watchdog_missed_beats_total").inc(max(len(ranks), 1))


def record_concurrency_check(races_found, gate, tripped=False):
    """One run of the ISSUE-10 concurrency analyzer: ``gate`` names the
    caller (``analyze``, ``run_batches``, a rewrite-bracket context).
    A finding at an enforcing gate journals an URGENT ``race-detected``
    event so the monitor's incident sequence shows the tripped gate."""
    if not telemetry_enabled():
        return
    _named(lambda n: _m.counter(n), "concurrency_checks_total").inc()
    if races_found:
        _named(lambda n: _m.counter(n), "races_found_total").inc(
            races_found)
        _journal.emit("race-detected", races=int(races_found),
                      gate=str(gate), tripped=bool(tripped),
                      trace=_trace_id())


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------

def set_collective_schedule(schedule, drift_key=None):
    """Install the compiled program's extracted per-ring schedule:
    gauges for the per-step shape, and the per-step totals the step
    hook turns into running counters.  ``schedule`` is
    ``{ring_id: [CollectiveEvent]}``."""
    global _collective_per_step
    if not telemetry_enabled():
        return
    per_step = []
    total_bytes = 0
    try:
        from ..static_analysis.cost import dtype_bytes
    except Exception:  # noqa: BLE001
        def dtype_bytes(_d):
            return 4
    for ring, events in (schedule or {}).items():
        label = str(ring)
        payload = sum(int(e.numel) * dtype_bytes(e.dtype)
                      for e in events)
        per_step.append((
            _m.counter("collective_launches_total", ring=label),
            _m.counter("collective_payload_bytes_total", ring=label),
            len(events), payload))
        total_bytes += payload
        _m.gauge("collective_launches_per_step", ring=label).set(
            len(events))
        _m.gauge("collective_payload_bytes_per_step", ring=label).set(
            payload)
    _collective_per_step = per_step
    if drift_key is not None and schedule:
        from . import drift as _drift

        _drift.monitor().observe_scheduled_ici(total_bytes,
                                               key=drift_key)


def collective_step_shape():
    """The installed schedule's per-ring per-step shape as span attrs:
    ``{"ring:<label>": "<launches>x/<payload_bytes>B"}`` (empty when no
    schedule is installed) — what the step span carries so a trace
    shows each step's collective launches without per-launch spans."""
    out = {}
    for launches_c, _payload_c, launches, payload in _collective_per_step:
        ring = dict(getattr(launches_c, "labels", ())).get("ring", "?")
        out["ring:%s" % ring] = "%dx/%dB" % (launches, payload)
    return out


# ---------------------------------------------------------------------------
# watchdog payload
# ---------------------------------------------------------------------------

def last_step_info():
    """``{"step": ..., "step_ms": ..., "ts": ...}`` of the newest
    completed step (None fields before the first) — what heartbeats
    embed so the monitor can flag a wedged-but-alive rank."""
    with _last_step_lock:
        return dict(_last_step)


def reset_runtime():
    """Clear cross-step state and cached handles (test isolation)."""
    global _collective_per_step
    with _last_step_lock:
        _last_step.update(step=None, step_ms=None, ts=None)
    _collective_per_step = []
    _snapshot_state.update(steps=0, last_write=0.0, step_times=0)
    _step_handles.clear()
    _jit_handles.clear()
    _named_handles.clear()
    _env_cache.clear()
    _moe_stats.clear()
    _moe_read.clear()
