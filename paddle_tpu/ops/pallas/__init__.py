"""Pallas TPU kernels for the hot ops.

The reference keeps a hand-tuned native kernel library for its hot loops
(x86 JIT codegen under ``paddle/fluid/operators/jit/``, fused CUDA kernels
under ``operators/fused/``).  The TPU-native analogue is Pallas: kernels
written against VMEM/MXU with explicit blocking, compiled by Mosaic.

Routing: :func:`use_pallas` is the ONE predicate every kernel (and the
autotune cache's backend key) asks.  Each entry point may still choose its
XLA composite for a shape the kernel does not cover — that is routing —
but a kernel that was selected runs as the kernel: on the chip it never
degrades to the composite or to interpret mode, and a lowering error is
an error.
"""

import collections
import contextlib
import os
import re
import threading
import warnings

import jax

_state = threading.local()


def device_platform():
    """Platform of the device programs run on (``"tpu"`` on the chip)."""
    return jax.devices()[0].platform


def use_pallas():
    """``(use, interpret)`` for the Pallas kernels.

    ``PADDLE_TPU_PALLAS=off`` forces the XLA composites, ``=interpret``
    forces the kernels through the Pallas interpreter (CPU correctness
    tests); otherwise the kernels engage exactly when the device is a TPU
    and the step being traced is not one GSPMD partitions
    (:func:`gspmd_partitioned`).
    """
    mode = os.environ.get("PADDLE_TPU_PALLAS", "") or "auto"
    if mode == "off":
        return False, False
    if mode == "interpret":
        return True, True
    if mode != "auto":
        raise ValueError(
            "PADDLE_TPU_PALLAS must be 'auto', 'off' or 'interpret', got %r"
            % mode)
    if getattr(_state, "partitioned", False):
        return False, False
    return device_platform() == "tpu", False


@contextlib.contextmanager
def gspmd_partitioned():
    """Active while a step that GSPMD partitions over several devices is
    traced (``_CompiledBlock`` with a multi-device mesh).  The TPU lowering
    refuses a Mosaic kernel there ("cannot be automatically partitioned"),
    so every op routes to its XLA composite; say so once, on the chip."""
    if use_pallas() == (True, False):  # kernels would have engaged
        warnings.warn(
            "this step is partitioned by GSPMD over a multi-device mesh: "
            "Mosaic kernels cannot be partitioned automatically, so the "
            "Pallas kernels are routed to their XLA composites here",
            stacklevel=2)
    was = getattr(_state, "partitioned", False)
    _state.partitioned = True
    try:
        yield
    finally:
        _state.partitioned = was


_KERNEL_CALL = re.compile(
    r'%([\w.\-]+?)(?:\.\d+)? = [^\n]*custom_call_target="tpu_custom_call"')


def pallas_kernels_in(hlo_text):
    """``Counter`` of the Mosaic kernels in a compiled module's text, by
    HLO instruction name — the ``name=`` each ``pallas_call`` here is
    given, or ``jvp_<name>_`` when traced through a ``custom_vjp`` rule.
    In a training step every kernel of a fused-LN or flash site carries
    the second form, the forward once a site (``jvp_fused_ln_fwd_``,
    ``jvp_flash_attention_fwd_``: the forward op keeps its ``jax.vjp``
    for the grad op, ``executor._run_ops_into_env``) beside the backward
    kernels; a program with no backward holds the plain names.  A plain
    AND a ``jvp_`` forward of one kernel in one step means a site's grad
    op ran the forward again (``grad_residual_sites_total``,
    ``path="recomputed"``).  A flash site inside a recompute region
    holds the plain names, the forward once too (what it computed
    crosses the region, ``path="kept_across_region"``); twice the
    sites' forwards there is the same doubling."""
    return collections.Counter(_KERNEL_CALL.findall(hlo_text))


# NOTE: deliberately NO `from .flash_attention import flash_attention`
# re-export: it would rebind the package attribute `flash_attention`
# from the submodule to the function, so `import
# paddle_tpu.ops.pallas.flash_attention as FA` (and the from-import of
# the name) silently yields the FUNCTION.  Import the function from the
# submodule: `from paddle_tpu.ops.pallas.flash_attention import
# flash_attention`.
from . import flash_attention  # noqa: E402,F401
from . import flash_decode  # noqa: E402,F401
from . import conv_bn_act  # noqa: E402,F401
