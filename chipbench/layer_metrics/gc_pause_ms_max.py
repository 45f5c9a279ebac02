"""The longest ``host.gc`` pause that starts in the measured window (0
when none reached the millisecond that makes a pause a span)."""

from chipbench import program_spans


def read(ctx):
    return program_spans.longest_gc_ms(ctx["state"])
