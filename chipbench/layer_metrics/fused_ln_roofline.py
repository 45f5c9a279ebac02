"""The least time the fused-LN calls' bytes could take at the HBM peak, over
the device time of every fused-LN event.  The forward kernel that the grad
op runs a second time moves no byte the algorithm needs: its time counts,
its bytes do not."""


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
