"""Host seconds of the set-up in the backend: the union of the
``compile.backend`` spans that start before the measured window, each an
XLA compile or a retrieval from the persistent compile cache (the span's
``source``), over every compile of the runners."""

from chipbench import compile_spans


def read(ctx):
    return compile_spans.setup_s(ctx["state"], ("backend",))
