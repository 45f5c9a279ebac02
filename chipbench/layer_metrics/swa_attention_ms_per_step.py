"""Device milliseconds a step under the Program-op tag ``swa_attention``
(the sliding layers' attention: projections, rotary, the three flash
kernels under the window, ``W_o``, the residual add), in every pass:
forward, the recompute region's re-run and its backward
(``xplane.program_op``)."""


def read(ctx):
    spent = ctx["trace"]["tag_s"].get("swa_attention")
    return None if spent is None else 1e3 * spent
