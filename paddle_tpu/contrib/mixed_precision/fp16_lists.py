"""Low-precision op lists (reference:
``python/paddle/fluid/contrib/mixed_precision/fp16_lists.py``).

TPU note: the low precision is **bfloat16**, not float16 — same exponent
range as fp32, so no loss scaling is required and the dynamic-loss-scaling
machinery of the reference degenerates to a no-op."""

# bf16 compute set.  TPU-native AMP runs the whole compute body in bf16 —
# matmuls on the MXU (fp32 accumulation via preferred_element_type in the
# lowerings) AND the elementwise/norm/shape glue between them.  Keeping the
# glue f32 (the reference's GPU-era policy) forces a bf16↔f32 ping-pong
# around every matmul that doubles HBM traffic and measurably loses MFU;
# numerically-sensitive internals (layer_norm stats, softmax exp) upcast to
# f32 inside their own lowerings, so whitelisting them is safe.
white_list = {
    # matmul-class
    "mul",
    "matmul",
    "conv2d",
    "depthwise_conv2d",
    "conv3d",
    "conv2d_transpose",
    "fused_dropout_add_ln",
    "fused_multihead_attention",
    "moe_experts",
    "ssd_scan",
    "causal_conv1d",
    # elementwise / activation glue
    "elementwise_add",
    "elementwise_sub",
    "elementwise_mul",
    "elementwise_div",
    "elementwise_max",
    "elementwise_min",
    "scale",
    "sum",
    "relu",
    "gelu",
    "tanh",
    "sigmoid",
    "swish",
    "leaky_relu",
    "dropout",
    "swiglu",
    "relu2",
    "rotary_embedding",
    # shape glue (cast-free but keeps dtype propagation consistent)
    "reshape",
    "reshape2",
    "transpose",
    "transpose2",
    "concat",
    "split",
    "stack",
    "slice",
    "squeeze",
    "squeeze2",
    "unsqueeze",
    "unsqueeze2",
    "expand",
    "pad",
    # normalization / attention softmax / fused loss (f32 internals in
    # the lowerings)
    "layer_norm",
    "rms_norm",
    "rms_norm_gated",
    "softmax",
    "softmax_with_cross_entropy",
}

# numerically sensitive ops: keep fp32 inputs (loss path + norms whose
# lowerings lack f32 internals)
black_list = {
    "cross_entropy",
    "log_softmax",
    "mean",
    "reduce_mean",
    "reduce_sum",
    "batch_norm",
    "exp",
    "log",
    "squared_l2_norm",
    # a near-tie between two experts' scores decides which one runs
    "moe_route",
}

# inputs of white-listed ops that stay float32: the RMSNorm scale beside
# its float32 statistics, the gates the router computed in float32, and
# what sets a scan's decays (a bf16 rounding of Δ or A would move
# exp(sum Δ A) over a chunk by whole factors) and its filter
fp32_slots = {
    "rms_norm": ("Scale",),
    "rms_norm_gated": ("Scale",),
    "moe_experts": ("Gate",),
    "ssd_scan": ("Dt", "ALog", "D", "DtBias"),
    "causal_conv1d": ("Filter", "Bias"),
}

# everything else follows its inputs
gray_list = set()


class AutoMixedPrecisionLists:
    def __init__(self, custom_white_list=None, custom_black_list=None):
        self.white_list = set(white_list)
        self.black_list = set(black_list)
        if custom_white_list:
            self.white_list |= set(custom_white_list)
        if custom_black_list:
            self.black_list |= set(custom_black_list)
        self.fp32_slots = dict(fp32_slots)
