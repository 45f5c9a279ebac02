"""Device milliseconds a step under the Program-op tag ``adam``: reading
and writing the float32 parameters, gradients and moments, of which the
held experts' are the largest part."""


def read(ctx):
    spent = ctx["trace"]["tag_s"].get("adam")
    return None if spent is None else 1e3 * spent
