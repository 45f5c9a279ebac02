"""Measure-and-learn sweep harness over the Pallas kernel knobs.

TVM's insight (arXiv:1802.04799) applied to this codebase's three knob
classes:

* **block/tile shapes** per (shape, dtype, backend) — flash attention's
  ``block_q``/``block_k``, the fused-LN and conv-BN epilogue row blocks;
* **engagement thresholds** — "from which size does the Pallas kernel
  beat XLA" (``PADDLE_TPU_FLASH_MIN_T`` was hand-set from a manual sweep;
  :func:`decide_threshold` derives it from measurements and caches it);
* **calibration factors** — measured-vs-predicted gain per fusion
  signature, fed back into :mod:`..static_analysis.cost` so the fusion
  gates weigh their predicted deltas by what silicon actually delivered.

Timing uses the PR-4 profiler phase events (``autotune.measure`` spans
show up in ``profiler.host_event_stats()`` and chrome traces) around a
``jax.block_until_ready`` window — median of ``repeats`` after a warmup
call that absorbs compilation.

Everything is cache-first: a second :func:`sweep` over the same
signature returns the stored winner WITHOUT re-timing (the contract
tier-1 tests assert), and ``PADDLE_TPU_AUTOTUNE=0`` turns every entry
point into its pre-autotune default.
"""

import time

from .cache import autotune_enabled, lookup, record, signature

__all__ = [
    "time_candidate", "sweep", "cached_params", "decide_threshold",
    "flash_min_t_decision", "record_flash_min_t", "calibration_factor",
    "calibrations",
]


def _median(xs):
    s = sorted(xs)
    n = len(s)
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


def time_candidate(runner, repeats=3, warmup=1, label="autotune.measure"):
    """Median wall-ms of ``runner()`` over ``repeats`` timed calls after
    ``warmup`` untimed ones (compilation), each bracketed by a profiler
    phase event and closed with ``jax.block_until_ready`` so async
    dispatch cannot leak work past the window."""
    import jax

    from .. import profiler

    for _ in range(max(warmup, 0)):
        jax.block_until_ready(runner())
    samples = []
    for _ in range(max(repeats, 1)):
        with profiler.record_event(label):
            t0 = time.perf_counter()
            jax.block_until_ready(runner())
            samples.append((time.perf_counter() - t0) * 1e3)
    return _median(samples)


def sweep(family, key, candidates, runner, baseline=None,
          predicted_gain=None, repeats=3, warmup=1):
    """Sweep ``candidates`` (list of params dicts) for one kernel site.

    ``runner(params) -> jax value`` executes the kernel with the
    candidate parameters; ``baseline() -> jax value`` (optional) is the
    XLA reference the kernel competes against.  ``key`` identifies the
    site (shape/dtype/...; the backend is appended automatically).

    Returns the cache entry::

        {"params", "measured_ms", "baseline_ms", "candidates",
         "predicted_gain", "measured_gain", "calibration", "backend"}

    Cache-first: an existing entry for the signature is returned verbatim
    with NO re-timing.  With autotune disabled the first candidate is
    returned untimed (the hand-set default)."""
    sig = sweep_signature(family, key)
    if not autotune_enabled():
        return {"params": dict(candidates[0]) if candidates else {},
                "cached": False, "disabled": True}
    hit = lookup(sig)
    if hit is not None:
        hit["cached"] = True
        return hit
    timed = []
    for params in candidates:
        ms = time_candidate(lambda p=params: runner(p), repeats=repeats,
                            warmup=warmup,
                            label="autotune.measure.%s" % family)
        timed.append((ms, dict(params)))
    if not timed:
        raise ValueError("sweep of %r got no candidates" % family)
    best_ms, best = min(timed, key=lambda t: t[0])
    entry = {
        "params": best,
        "measured_ms": round(best_ms, 4),
        "candidates": [{"params": p, "ms": round(ms, 4)}
                       for ms, p in timed],
        "backend": _backend(),
    }
    if baseline is not None:
        base_ms = time_candidate(baseline, repeats=repeats, warmup=warmup,
                                 label="autotune.measure.%s.baseline"
                                       % family)
        entry["baseline_ms"] = round(base_ms, 4)
        measured_gain = base_ms / best_ms if best_ms > 0 else 0.0
        entry["measured_gain"] = round(measured_gain, 4)
        if predicted_gain:
            entry["predicted_gain"] = round(float(predicted_gain), 4)
            # calibration = what silicon delivered / what the static
            # model promised; the fusion gates multiply their predicted
            # deltas by this factor (cost.py exposes it in --bench-json)
            entry["calibration"] = round(
                measured_gain / float(predicted_gain), 4)
    record(sig, entry)
    entry["cached"] = False
    return entry


def sweep_signature(family, key):
    """The cache signature a :func:`sweep` of ``(family, key)`` uses —
    ``key`` plus the active backend."""
    key = dict(key or {})
    key.setdefault("backend", _backend())
    return signature(family, **key)


def _backend():
    """The backend half of every cache signature: the platform the Pallas
    kernels route by (``ops.pallas.device_platform``)."""
    from ..ops.pallas import device_platform

    return device_platform()


def cached_params(family, default_params, **key):
    """The cached winning params for ``(family, key)`` merged over
    ``default_params`` — the one-liner kernels use to pick block shapes.
    Defaults come back untouched on a miss or with autotune disabled."""
    out = dict(default_params or {})
    if not autotune_enabled():
        return out
    hit = lookup(sweep_signature(family, key))
    if hit and isinstance(hit.get("params"), dict):
        out.update(hit["params"])
    return out


def cached_block_cap(family, env_var, param, default, **key):
    """Shared block-size resolution for the Pallas kernels: env cap
    (manual override) → cached sweep winner for ``(family, key)`` →
    the hand-set default.  One implementation so the precedence rule
    can't drift between kernels; callers still enforce their own
    divisibility/alignment on the returned cap."""
    import os

    env = os.environ.get(env_var, "").strip()
    if env:
        try:
            return int(env)
        except ValueError:
            return default
    try:
        return int(cached_params(family, {param: default}, **key)[param])
    except Exception:  # noqa: BLE001 - autotune is best-effort
        return default


# ---------------------------------------------------------------------------
# threshold decisions (the decide_flash_min_t generalization)
# ---------------------------------------------------------------------------

def decide_threshold(rows):
    """Generalized engagement-threshold rule (tools/decide_flash_min_t):
    ``rows`` maps a scalar knob value (e.g. sequence length T) to
    ``(candidate_ms, baseline_ms)``.  Returns the smallest knob value
    where the candidate wins AND keeps winning at every larger measured
    value, or None when it never cleanly wins."""
    wins = {int(t): (c < b) for t, (c, b) in rows.items()
            if c is not None and b is not None}
    for t in sorted(wins):
        if wins[t] and all(wins[u] for u in wins if u >= t):
            return t
    return None


_FLASH_MIN_T_FAMILY = "flash_min_t"


def flash_min_t_decision():
    """The cached flash engagement threshold for this backend, or None.
    Consumed by ``ops.pallas.flash_attention.flash_min_t()`` when
    ``PADDLE_TPU_FLASH_MIN_T`` is unset — the env var stays the manual
    override, the cache replaces the hand-set default."""
    hit = lookup(sweep_signature(_FLASH_MIN_T_FAMILY, {}))
    if hit is None:
        return None
    try:
        t = int(hit.get("params", {}).get("min_t"))
    except (TypeError, ValueError):
        return None
    return t if t > 0 else None


def record_flash_min_t(min_t, rows=None, backend=None):
    """Persist a flash engagement threshold (from
    ``tools/decide_flash_min_t.py --write-cache`` or an on-chip sweep).
    ``rows``: the measurement table the decision came from, stored for
    provenance.  ``backend``: which backend the MEASUREMENTS came from
    (default: this process's) — the tool routinely parses on-chip sweep
    artifacts from a CPU workstation, and a decision filed under the
    wrong backend would silently no-op where it matters."""
    backend = str(backend).lower() if backend else _backend()
    entry = {"params": {"min_t": int(min_t)}, "backend": backend}
    if rows:
        entry["rows"] = {str(t): [c, b] for t, (c, b) in rows.items()}
    return record(signature(_FLASH_MIN_T_FAMILY, backend=backend), entry)


_DECODE_MIN_T_FAMILY = "decode_min_t"


def decode_min_t_decision():
    """The cached flash-*decode* engagement threshold for this backend,
    or None.  Consumed by ``ops.pallas.flash_decode.decode_min_t()``
    when ``PADDLE_TPU_DECODE_MIN_T`` is unset — same contract as
    :func:`flash_min_t_decision` for the prefill kernel."""
    hit = lookup(sweep_signature(_DECODE_MIN_T_FAMILY, {}))
    if hit is None:
        return None
    try:
        t = int(hit.get("params", {}).get("min_t"))
    except (TypeError, ValueError):
        return None
    return t if t > 0 else None


def record_decode_min_t(min_t, rows=None, backend=None):
    """Persist a decode engagement threshold (bench ``--child decode``
    sweep or a manual on-chip run); mirrors :func:`record_flash_min_t`
    including the explicit-backend provenance rule."""
    backend = str(backend).lower() if backend else _backend()
    entry = {"params": {"min_t": int(min_t)}, "backend": backend}
    if rows:
        entry["rows"] = {str(t): [c, b] for t, (c, b) in rows.items()}
    return record(signature(_DECODE_MIN_T_FAMILY, backend=backend), entry)


# ---------------------------------------------------------------------------
# calibration factors (the cost-model feedback loop)
# ---------------------------------------------------------------------------

def calibration_factor(sig):
    """Measured/predicted gain for one fusion signature (1.0 when
    unknown or autotune is disabled).  The fusion gates multiply their
    predicted deltas by this before comparing against thresholds."""
    hit = lookup(sig)
    if not hit:
        return 1.0
    try:
        f = float(hit.get("calibration", 1.0))
    except (TypeError, ValueError):
        return 1.0
    return f if f > 0 else 1.0


def calibrations():
    """Every signature with a recorded calibration factor —
    what ``analyze_program --bench-json`` surfaces."""
    from .cache import entries

    out = {}
    for sig, e in entries().items():
        try:
            f = float(e.get("calibration"))
        except (TypeError, ValueError):
            continue
        if f > 0:
            out[sig] = f
    return out
