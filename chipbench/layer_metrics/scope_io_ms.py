"""Median host milliseconds in ``gather_state`` plus ``apply_results``
(the step's state out of the scope and back into it) over the kept steps of
the measured window."""

from chipbench import program_spans


def read(ctx):
    return program_spans.phase_ms(
        ctx["state"], "gather_state", "apply_results")
