"""Median host milliseconds in the ``feed_stage`` phase (``FeedCache``
fingerprint and compare, the copy to the device, the feed-shape check) over
the kept steps of the measured window."""

from chipbench import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx["state"], "feed_stage")
