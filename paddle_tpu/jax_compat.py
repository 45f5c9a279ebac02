"""The jax names the codebase reaches through one module.

Written for the installed jax (0.9): ``jax.shard_map`` with the
``check_vma`` spelling, ``jax.lax.axis_size``, and
``Lowered.as_text(debug_info=...)``.  The module stays so that call sites
(and tests) keep one import point for them.
"""

import jax

__all__ = ["shard_map", "lowered_as_text", "axis_size"]


def shard_map(f, mesh=None, in_specs=None, out_specs=None,
              check_vma=None, **kw):
    """``jax.shard_map`` with positional ``mesh``/``in_specs``/
    ``out_specs`` (the call shape used throughout ``parallel/``)."""
    if check_vma is not None:
        kw["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def axis_size(axis_name):
    """Static size of a mapped mesh axis."""
    return jax.lax.axis_size(axis_name)


def lowered_as_text(lowered, debug_info=False):
    """``jax.stages.Lowered.as_text``; ``debug_info=True`` keeps the
    ``named_scope`` location metadata the profiler tooling greps for."""
    return lowered.as_text(debug_info=debug_info)
