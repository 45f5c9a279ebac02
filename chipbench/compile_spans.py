"""The set-up's compile work as the program itself records it.

jax's trace, lowering to MLIR and backend compile (or persistent-cache
retrieval) of everything the runners dispatch are ``<runner>.compile.trace``,
``.lower`` and ``.backend`` spans (``paddle_tpu.observability.tracing``
``watch_compiles``), children of the phase that paid for them; a step that
lowered or compiled is always kept, so the ring holds every one from set-up.
Nested events are already counted once by the program; the readers still
take the union of the spans' intervals, so no instant counts twice.

A program that has no such spans (one from before they were added) gives
nothing to read: None, and no error.
"""

from chipbench import program_spans
from chipbench import xplane

STAGES = ("trace", "lower", "backend")


def stage_of(record):
    """``trace``, ``lower`` or ``backend`` for a runner's compile-stage
    span, else None."""
    runner, _, rest = record.get("name", "").partition(".")
    kind, _, stage = rest.rpartition(".")
    if runner in program_spans.RUNNERS and kind == "compile" \
            and stage in STAGES:
        return stage
    return None


def setup_s(state, stages):
    """Host seconds of the set-up in ``stages``: the union of the
    intervals of their spans that start before the measured window opens
    (``t_window``, on the spans' ``perf_counter_ns`` clock), over every
    compile of the runners.  None where the ring holds no stage span."""
    spans = [r for r in program_spans.ring_records()
             if "t0_ns" in r and stage_of(r) is not None]
    if not spans:
        return None
    before = state["t_window"] * 1e9
    covered = xplane.union(
        (r["t0_ns"], r["t0_ns"] + r["dur_ms"] * 1e6) for r in spans
        if stage_of(r) in stages and r["t0_ns"] < before)
    return xplane.length(covered) / 1e9
