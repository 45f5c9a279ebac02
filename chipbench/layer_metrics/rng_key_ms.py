"""Median host milliseconds in the ``rng_key`` phase (the base key built
and the step folded in) over the kept steps of the measured window."""

from chipbench import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx["state"], "rng_key")
