"""Host seconds around the program builder and the first fusion resolve."""


def read(ctx):
    return ctx["state"]["clocks"]["build_s"]
