"""AMP optimizer decorator (reference:
``python/paddle/fluid/contrib/mixed_precision/decorator.py:27``
OptimizerWithMixedPrecision: fp16 casts by white/black list, dynamic loss
scaling, fp32 master weights).

TPU-native: bf16 instead of fp16.  The program rewrite inserts `cast` ops in
front of white-listed (matmul-class) ops, so the MXU consumes bf16 while
params remain fp32 masters; the cast op's vjp casts grads back to fp32, which
IS the master-weight scheme.  bf16's fp32-equal exponent range makes loss
scaling unnecessary — the loss-scaling knobs are accepted and ignored."""

from ... import unique_name
from ...framework import default_main_program
from .fp16_lists import AutoMixedPrecisionLists

__all__ = ["decorate", "OptimizerWithMixedPrecision", "rewrite_program_bf16"]


def rewrite_program_bf16(program, amp_lists=None):
    """Insert bf16 casts before white-listed ops and fp32 casts before
    black-listed ops (reference fp16_utils.py rewrite_program), in the
    global block and in the regions of its ``recompute_block`` ops
    (``fluid.layers.recompute()``: a region's casts are part of what its
    grad op re-runs, so a weight's bf16 copy lives for one region)."""
    amp_lists = amp_lists or AutoMixedPrecisionLists()
    _rewrite_block_bf16(program, program.global_block(), amp_lists)
    program._bump_version()
    return program


def _rewrite_block_bf16(program, block, amp_lists):
    from ...framework import Operator

    cast_cache = {}  # (var, dtype) -> cast var name
    new_ops = []

    def cast_input(op, target_dtype, from_dtypes, keep=()):
        for slot, names in op.inputs.items():
            if slot in keep:
                continue
            new_names = []
            for n in names:
                var = block._find_var_recursive(n)
                if var is None or var.dtype not in from_dtypes:
                    new_names.append(n)
                    continue
                key = (n, target_dtype)
                if key not in cast_cache:
                    cast_name = unique_name.generate(n + ".cast_" + target_dtype)
                    block.create_var(
                        name=cast_name, shape=var.shape, dtype=target_dtype,
                        persistable=False, stop_gradient=var.stop_gradient,
                    )
                    cast_op = Operator(
                        block, "cast",
                        {"X": [n]}, {"Out": [cast_name]},
                        {"in_dtype": var.dtype, "out_dtype": target_dtype},
                    )
                    new_ops.append(cast_op)
                    cast_cache[key] = cast_name
                new_names.append(cast_cache[key])
            op.inputs[slot] = new_names

    for op in block.ops:
        if op.type == "recompute_block":
            _rewrite_block_bf16(
                program, program.block(int(op.attrs["sub_block"])),
                amp_lists)
        elif op.type in amp_lists.white_list:
            cast_input(op, "bfloat16", ("float32",),
                       keep=amp_lists.fp32_slots.get(op.type, ()))
            # downstream vars produced by this op are bf16 at runtime
            for name in op.output_arg_names:
                v = block._find_var_recursive(name)
                if v is not None and v.dtype == "float32":
                    v.dtype = "bfloat16"
        elif op.type in amp_lists.black_list:
            cast_input(op, "float32", ("bfloat16",))
        new_ops.append(op)
    block.ops = new_ops


class OptimizerWithMixedPrecision:
    def __init__(self, optimizer, amp_lists=None, init_loss_scaling=1.0,
                 use_dynamic_loss_scaling=False, incr_every_n_steps=1000,
                 decr_every_n_nan_or_inf=2, incr_ratio=2.0, decr_ratio=0.8):
        self._optimizer = optimizer
        self._amp_lists = amp_lists or AutoMixedPrecisionLists()
        self._loss_scaling = init_loss_scaling  # parity only; bf16 needs none

    def backward(self, loss, **kwargs):
        rewrite_program_bf16(loss.block.program, self._amp_lists)
        return self._optimizer.backward(loss, **kwargs)

    def apply_gradients(self, params_grads):
        return self._optimizer.apply_gradients(params_grads)

    def minimize(self, loss, startup_program=None, parameter_list=None,
                 no_grad_set=None):
        rewrite_program_bf16(loss.block.program, self._amp_lists)
        return self._optimizer.minimize(
            loss, startup_program, parameter_list, no_grad_set
        )


def decorate(optimizer, amp_lists=None, init_loss_scaling=1.0,
             incr_every_n_steps=1000, decr_every_n_nan_or_inf=2,
             incr_ratio=2.0, decr_ratio=0.8,
             use_dynamic_loss_scaling=False):
    return OptimizerWithMixedPrecision(
        optimizer, amp_lists, init_loss_scaling, use_dynamic_loss_scaling,
        incr_every_n_steps, decr_every_n_nan_or_inf, incr_ratio, decr_ratio,
    )
