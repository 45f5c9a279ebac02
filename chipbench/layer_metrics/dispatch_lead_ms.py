"""From the traced window's xplane alone: median over steps and devices
of the start of the step module's execution less the end of that step's
``*.dispatch`` annotation on the host plane, joined by the annotation's
``step``.  How long a dispatched step waits in the device's queue: about the
step less the host's share while the device is the limit, the launch latency
when the host is."""

import json

from chipbench import program_spans


def read(ctx):
    trace = program_spans.traced_annotations()
    if not trace:
        return None
    why = []
    value = program_spans.dispatch_lead_ms(trace, why)
    if value is None:       # on a line of its own, as run.py says things
        print(json.dumps({"dispatch_lead_ms": None, "why": why}),
              flush=True)
    return value
