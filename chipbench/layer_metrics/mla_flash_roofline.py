"""The least time causal attention at ``d_qk`` 192, ``d_v`` 128 could take
(the larger of its operations over the bf16 peak and its bytes over the HBM
peak; half the square, no recomputation), over the device time of every
flash-attention event, the region's second forward among them."""


def read(ctx):
    spent = sum(s for k, s in ctx["trace"]["kernel_s"].items()
                if "flash_attention_" in k)
    flops = ctx["flops"]
    if not spent or not hasattr(flops, "flash_flops_per_step"):
        return None
    cell, peaks = ctx["cell"], ctx["peaks"]
    least = max(
        flops.flash_flops_per_step(cell["config"], cell["traffic"])
        / peaks["bf16_flops_per_s"],
        flops.flash_bytes_per_step(cell["config"], cell["traffic"])
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least / spent
