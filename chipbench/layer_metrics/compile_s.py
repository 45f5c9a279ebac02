"""Host seconds around the first ``Executor.run`` of the step program, to
its loss: trace, lower, compile or persistent-cache hit, first step."""


def read(ctx):
    return ctx["state"]["clocks"]["compile_s"]
