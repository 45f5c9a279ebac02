"""Model operations per example times the examples per second of this run's
own untraced window, over chips times the bf16 peak.  Where the
configuration's operations follow the program's counters (the rows routed to
the experts held here), they are the counters of that window's steps alone:
what they read where the traced window opened less what they read where the
measured one did."""


def read(ctx):
    cell, flops = ctx["cell"], ctx["flops"]
    args = (cell["config"], cell["traffic"])
    if hasattr(flops, "rows_per_token"):
        counted = ctx["state"]["counters"]
        args += (flops.rows_per_token(cell["config"], flops.counted_between(
            counted["window"], counted["trace"])),)
    return (100.0 * flops.train_flops_per_example(*args)
            * ctx["measured"]["train_examples_per_s"]
            / (cell["workload"]["chips"] * ctx["peaks"]["bf16_flops_per_s"]))
