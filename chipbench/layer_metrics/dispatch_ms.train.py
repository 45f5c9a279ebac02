"""Median host milliseconds inside ``Executor.run(..., return_numpy=False)``
over the steps of the untraced window."""

import statistics


def read(ctx):
    loop = ctx["state"]
    inside = loop["dispatch_s"][loop["first"]:loop["first"] + loop["steps"]]
    return 1e3 * statistics.median(inside)
