"""Median self time of the ``step`` span over the kept steps of the
measured window: its duration less what its children cover."""

from chipbench import program_spans


def read(ctx):
    return program_spans.self_ms(ctx["state"])
