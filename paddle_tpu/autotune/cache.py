"""On-disk autotune cache — measured kernel decisions keyed by fusion
signature.

The TVM measure-and-learn loop (arXiv:1802.04799) splits autotuning into
a *measurer* (run candidates on silicon) and a *cost model* that learns
from the measurements.  This module is the persistence layer between the
two: every sweep the harness (:mod:`.harness`) runs writes one entry —
the winning kernel parameters, the measured times, and the
predicted-vs-measured calibration factor — keyed by a canonical
signature string ``family|k=v|...`` that includes shape, dtype and
backend, so a decision made on a v5e never leaks onto a CPU run.

Durability contract (the resilience-checkpoint discipline, PR 2):

* writes are ATOMIC — stage to a same-directory temp file, ``os.replace``
  over the real one; a torn write can never half-update the cache;
* reads are CORRUPT-SAFE — a truncated/garbage/wrong-schema file warns
  once and behaves as an empty cache (defaults everywhere, no crash);
  the next :func:`record` rewrites it whole;
* the schema is VERSIONED — ``{"schema": 1, ...}``; an entry written by
  a future incompatible schema is ignored rather than misread.

Env knobs:

* ``PADDLE_TPU_AUTOTUNE=0`` — global kill switch: every lookup misses,
  nothing is written, all block sizes / thresholds fall back to their
  hand-set defaults (bit-exact pre-autotune behavior);
* ``PADDLE_TPU_AUTOTUNE_CACHE`` — cache file path (default
  ``<checkout>/.autotune_cache/autotune-v1.json``: inside the checkout,
  so what a run decides never depends on a file outside it).
"""

import json
import os
import threading
import warnings

__all__ = [
    "SCHEMA_VERSION", "autotune_enabled", "cache_path", "signature",
    "lookup", "record", "entries", "state_token", "reset",
]

SCHEMA_VERSION = 1

_lock = threading.RLock()
# {path: {"sigs": {...}, "mtime": float}} — loaded once per path per
# process; record() bumps _generation so fusion signatures (part of the
# executor's jit cache key) see in-process cache changes
_loaded = {}
_generation = 0
_warned_paths = set()


def autotune_enabled():
    """Kill switch: ``PADDLE_TPU_AUTOTUNE=0`` disables every cache read
    AND write — block sizes, thresholds and fusion gates then use their
    hand-set defaults exactly as before this subsystem existed."""
    return os.environ.get("PADDLE_TPU_AUTOTUNE", "1") != "0"


def cache_path():
    """``PADDLE_TPU_AUTOTUNE_CACHE`` or the per-checkout default."""
    p = os.environ.get("PADDLE_TPU_AUTOTUNE_CACHE", "").strip()
    if p:
        return p
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".autotune_cache",
                        "autotune-v%d.json" % SCHEMA_VERSION)


def signature(family, **key):
    """Canonical signature string for one tuning decision:
    ``family|k1=v1|k2=v2`` with sorted keys.  Callers include shape,
    dtype and backend in ``key`` so decisions never cross devices."""
    parts = [str(family)]
    for k in sorted(key):
        v = key[k]
        if isinstance(v, (list, tuple)):
            v = "x".join(str(x) for x in v)
        parts.append("%s=%s" % (k, v))
    return "|".join(parts)


def _parse_file(path):
    """Read + validate the cache file; returns the signature dict.
    Corrupt or wrong-schema content degrades to {} with one warning per
    path per process (the checkpoint-skip-torn-version discipline)."""
    try:
        with open(path) as f:
            data = json.load(f)
        if not isinstance(data, dict) \
                or data.get("schema") != SCHEMA_VERSION \
                or not isinstance(data.get("entries"), dict):
            raise ValueError("bad schema %r" % (
                data.get("schema") if isinstance(data, dict) else None))
        return dict(data["entries"])
    except FileNotFoundError:
        return {}
    except Exception as e:  # noqa: BLE001 - corrupt cache must not crash
        if path not in _warned_paths:
            _warned_paths.add(path)
            warnings.warn(
                "paddle_tpu autotune cache %s is unreadable (%s) — "
                "falling back to default kernel parameters; the next "
                "sweep rewrites it" % (path, e), stacklevel=3)
        return {}


def _load(path):
    with _lock:
        cached = _loaded.get(path)
        try:
            mtime = os.stat(path).st_mtime_ns
        except OSError:
            mtime = None
        if cached is not None and cached["mtime"] == mtime:
            return cached["sigs"]
        sigs = _parse_file(path)
        _loaded[path] = {"sigs": sigs, "mtime": mtime}
        return sigs


def lookup(sig):
    """The cached entry dict for ``sig``, or None (miss / disabled /
    corrupt file).  Pure read — never touches the file system when the
    kill switch is set."""
    if not autotune_enabled():
        return None
    entry = _load(cache_path()).get(sig)
    return dict(entry) if isinstance(entry, dict) else None


def entries():
    """All cached entries ``{sig: entry}`` (empty when disabled)."""
    if not autotune_enabled():
        return {}
    return {k: dict(v) for k, v in _load(cache_path()).items()
            if isinstance(v, dict)}


def record(sig, entry):
    """Atomically merge ``{sig: entry}`` into the cache file.  No-op
    when the kill switch is set.  Returns the entry written."""
    global _generation
    if not autotune_enabled():
        return dict(entry)
    path = cache_path()
    with _lock:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        # fresh read-merge-write so concurrent processes mostly compose
        sigs = _parse_file(path)
        sigs[sig] = dict(entry)
        tmp = "%s.tmp.%d" % (path, os.getpid())
        with open(tmp, "w") as f:
            json.dump({"schema": SCHEMA_VERSION, "entries": sigs}, f,
                      indent=1, sort_keys=True)
        os.replace(tmp, path)
        try:
            mtime = os.stat(path).st_mtime_ns
        except OSError:  # pragma: no cover
            mtime = None
        _loaded[path] = {"sigs": sigs, "mtime": mtime}
        _generation += 1
    return dict(entry)


def state_token():
    """Hashable token identifying the cache state this process sees —
    folded into the fusion-config signature (hence the executor's jit
    cache key), so an in-process sweep invalidates resolved program
    clones that were gated on the old decisions."""
    if not autotune_enabled():
        return ("autotune-off",)
    path = cache_path()
    # load-backed (one os.stat; parse only on mtime change): the token
    # must be STABLE across "before first lookup" and "after" — a token
    # that flips when a lookup first touches the file would cost every
    # program one spurious fusion-clone rebuild
    _load(path)
    with _lock:
        cached = _loaded.get(path)
        mtime = cached["mtime"] if cached is not None else None
    return (path, mtime, _generation)


def reset():
    """Drop the in-process cache state (test isolation)."""
    global _generation
    with _lock:
        _loaded.clear()
        _warned_paths.clear()
        _generation += 1
