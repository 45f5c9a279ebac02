"""Per step and device, milliseconds of collective ops during which no
other op runs on that device."""


def read(ctx):
    trace = ctx["trace"]
    if not trace["collective_s"]:
        return None
    return 1e3 * trace["collective_exposed_s"]
