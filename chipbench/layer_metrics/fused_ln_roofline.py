"""The least time the fused-LN calls' bytes could take at the HBM peak, over
the device time of every fused-LN event: one forward and one backward
kernel a site (since PR 27 the grad op takes the forward's residuals; a
kernel that a step ran beside them would count by its time, not its
bytes)."""


def read(ctx):
    trace = ctx["trace"]
    spent = sum(s for k, s in trace["kernel_s"].items()
                if "fused_ln_" in k)
    if not spent:
        return None
    cell = ctx["cell"]
    need = ctx["flops"].fused_ln_bytes_per_step(cell["config"],
                                                cell["traffic"])
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / spent
