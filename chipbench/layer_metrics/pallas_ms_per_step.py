"""Device milliseconds per step in events named after a ``tpu_custom_call``
of the compiled step (the names ``ops.pallas.pallas_kernels_in`` finds)."""


def read(ctx):
    return 1e3 * sum(ctx["trace"]["kernel_s"].values())
