"""1 - the union of device-op intervals over the steady traced window."""


def read(ctx):
    return 100.0 * ctx["trace"]["idle_share"]
