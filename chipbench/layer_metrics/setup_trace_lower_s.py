"""Host seconds of the set-up that jax spent tracing the runners' programs
to jaxprs and lowering them to MLIR: the union of the ``compile.trace`` and
``compile.lower`` spans that start before the measured window, over every
compile (the startup program, the step, eager functions)."""

from chipbench import compile_spans


def read(ctx):
    return compile_spans.setup_s(ctx["state"], ("trace", "lower"))
