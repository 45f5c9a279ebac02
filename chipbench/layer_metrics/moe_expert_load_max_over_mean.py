"""The fullest held expert's rows over the held experts' mean, from the
program's device counters over the run so far; the mean over the expert
layers.  1 is an even load; the grouped products take as long as the
rows in all, but under expert parallelism the fullest chip sets the pace."""


def read(ctx):
    flops = ctx["flops"]
    if not hasattr(flops, "counted_rows"):
        return None
    ratios = [max(c["rows"]) * len(c["rows"]) / sum(c["rows"])
              for c in flops.counted_rows(ctx["cell"]["config"]).values()
              if sum(c["rows"])]
    return sum(ratios) / len(ratios) if ratios else None
