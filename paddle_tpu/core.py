"""Core runtime types: places, dtypes, VarType.

The reference implements these natively (``paddle/fluid/platform/place.h``,
``paddle/fluid/framework/framework.proto:105`` VarType) and exposes them via
pybind (``paddle/fluid/pybind/pybind.cc``).  On TPU the device abstraction is
jax's; a Place here is a thin selector that maps onto a ``jax.Device`` (or the
whole default device set), so `Executor(place)` keeps the reference API shape
while jit/XLA own actual placement.
"""

import enum

import numpy as np


def configure_compile_cache():
    """Place JAX's persistent compilation cache; returns the directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set the cache is left entirely
    to JAX and nothing is set in code.  Otherwise it lives at
    ``<checkout>/.jax_cache`` — a fixed path, because the path is part of
    the cache key and a directory that moves never hits.  Called by the
    entry points that compile on the chip (``chip_smoke.py``, ``bench.py``
    children, ``tools/``) before their first compile."""
    import os

    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    cache_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    return cache_dir


def require_tpu(what):
    """The first device, for code that measures or validates the chip
    (``bench.py`` chip children, ``tools/``): any backend but the TPU is
    an error here, never a fallback — ``TPUPlace()`` itself resolves to
    whatever backend there is, because tests drive it on the CPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            "%s runs on the chip only and JAX reports platform %r — "
            "refusing to run" % (what, dev.platform))
    return dev


class VarDesc:
    """Namespace mirroring the reference's VarDesc proto enums
    (``framework.proto:105-163``)."""

    class VarType(enum.IntEnum):
        # tensor types
        BOOL = 0
        INT16 = 1
        INT32 = 2
        INT64 = 3
        FP16 = 4
        FP32 = 5
        FP64 = 6
        SIZE_T = 19
        UINT8 = 20
        INT8 = 21
        BF16 = 22
        # container / special types
        LOD_TENSOR = 7
        SELECTED_ROWS = 8
        FEED_MINIBATCH = 9
        FETCH_LIST = 10
        STEP_SCOPES = 11
        LOD_RANK_TABLE = 12
        LOD_TENSOR_ARRAY = 13
        PLACE_LIST = 14
        READER = 15
        RAW = 17
        TUPLE = 18


_DTYPE_TO_VARTYPE = {
    np.dtype("bool"): VarDesc.VarType.BOOL,
    np.dtype("int16"): VarDesc.VarType.INT16,
    np.dtype("int32"): VarDesc.VarType.INT32,
    np.dtype("int64"): VarDesc.VarType.INT64,
    np.dtype("float16"): VarDesc.VarType.FP16,
    np.dtype("float32"): VarDesc.VarType.FP32,
    np.dtype("float64"): VarDesc.VarType.FP64,
    np.dtype("uint8"): VarDesc.VarType.UINT8,
    np.dtype("int8"): VarDesc.VarType.INT8,
}

_VARTYPE_TO_DTYPE = {v: k for k, v in _DTYPE_TO_VARTYPE.items()}


def convert_np_dtype_to_dtype_(dtype):
    """Normalize a user dtype spec (str / np.dtype / VarType) to a canonical
    string.  'bfloat16' is kept as a string since numpy has no native bf16."""
    if isinstance(dtype, VarDesc.VarType):
        if dtype == VarDesc.VarType.BF16:
            return "bfloat16"
        return _VARTYPE_TO_DTYPE[dtype].name
    if isinstance(dtype, str):
        if dtype in ("bfloat16", "bf16"):
            return "bfloat16"
        return np.dtype(dtype).name
    return np.dtype(dtype).name


def dtype_is_floating(dtype):
    d = convert_np_dtype_to_dtype_(dtype)
    return d in ("float16", "float32", "float64", "bfloat16")


class Place:
    """Base device selector."""

    _kind = "base"

    def __init__(self, device_id=0):
        self._device_id = int(device_id)

    def __eq__(self, other):
        return type(self) is type(other) and self._device_id == other._device_id

    def __hash__(self):
        return hash((type(self).__name__, self._device_id))

    def __repr__(self):
        return "%s(%d)" % (type(self).__name__, self._device_id)

    def jax_device(self):
        """Resolve to a concrete jax.Device (lazy import keeps `core` light)."""
        import jax

        if isinstance(self, CPUPlace):
            devs = jax.devices("cpu")
        else:
            devs = jax.devices()
        return devs[self._device_id % len(devs)]


class CPUPlace(Place):
    _kind = "cpu"


class TPUPlace(Place):
    """The native accelerator place of this framework (reference analogue:
    CUDAPlace, ``platform/place.h``)."""

    _kind = "tpu"


# Alias for source compatibility with reference user scripts; on this
# framework "CUDA" places simply select the default jax accelerator.
CUDAPlace = TPUPlace


class CUDAPinnedPlace(Place):
    _kind = "pinned"


def is_compiled_with_cuda():
    return False


def is_compiled_with_tpu():
    return True


def get_device_count():
    import jax

    return jax.device_count()


# ---------------------------------------------------------------------------
# Flag system (reference: gflags exposed through __bootstrap__ forwarding
# whitelisted FLAGS_* env vars, python/paddle/fluid/__init__.py:124-199).
# TPU-native: the debugging flags map onto jax config switches.
# ---------------------------------------------------------------------------

_flags = {
    # NaN/Inf debugging (reference FLAGS_check_nan_inf: per-op nan printers
    # via lodtensor_printer; here jax re-runs the offending op de-optimized
    # and raises with the op name — same diagnosis, compiler-native)
    "FLAGS_check_nan_inf": False,
    # bit-exact cross-platform determinism (reference FLAGS_cpu_deterministic)
    "FLAGS_cpu_deterministic": False,
    "FLAGS_benchmark": False,
}


def set_flags(flags):
    """Set runtime debugging flags (reference ``fluid.set_flags``)."""
    import jax

    flags = dict(flags)
    unknown = [n for n in flags if n not in _flags]
    if unknown:
        raise KeyError("unknown flag(s) %r (known: %s)"
                       % (unknown, sorted(_flags)))
    for name, value in flags.items():
        _flags[name] = value
        if name == "FLAGS_check_nan_inf":
            jax.config.update("jax_debug_nans", bool(value))
        elif name == "FLAGS_cpu_deterministic" and value:
            import os

            os.environ.setdefault("PADDLE_TPU_RNG_IMPL", "threefry2x32")


def get_flags(names):
    if isinstance(names, str):
        return {names: _flags[names]}
    return {n: _flags[n] for n in names}


def _bootstrap_flags():
    """Forward FLAGS_* env vars into the flag registry at import, the
    reference ``__bootstrap__`` pattern."""
    import os

    for name in list(_flags):
        raw = os.environ.get(name)
        if raw is None:
            continue
        set_flags({name: raw.lower() in ("1", "true", "yes", "on")})


_bootstrap_flags()
