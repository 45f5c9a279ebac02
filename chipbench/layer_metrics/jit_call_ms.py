"""Median host milliseconds in the ``dispatch`` phase (the jitted call
alone, which returns when the step is enqueued) over the kept steps of the
measured window."""

from chipbench import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx["state"], "dispatch")
