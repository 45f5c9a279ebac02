"""The fullest held expert's rows over the held experts' mean, from the
program's device counters over the steps of the measured window alone (what
they read where the traced window opened less what they read where the
measured one did); the mean over the expert layers.  1 is an even load; the
grouped products take as long as the rows in all, but under expert
parallelism the fullest chip sets the pace."""


def read(ctx):
    flops = ctx["flops"]
    if not hasattr(flops, "counted_between"):
        return None
    counted = ctx["state"]["counters"]
    ratios = [max(c["rows"]) * len(c["rows"]) / sum(c["rows"])
              for c in flops.counted_between(counted["window"],
                                             counted["trace"]).values()
              if sum(c["rows"])]
    return sum(ratios) / len(ratios) if ratios else None
