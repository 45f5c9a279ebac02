"""The least time the grouped products over the held relu² experts could
take for the rows the program's counters show were routed here in the
traced window's own steps (what they read where it closed less what they
read where it opened; the larger of the rows' operations over the bf16
peak and their bytes over the HBM peak, from the configuration's
``flops`` module: six useful products a layer, up and down forward and
the two transposes of each), over the device time of the ``ragged-dot``
kernels in that window.  The reading is ``moe_experts_roofline``'s, for
the cells whose experts have no gate; a trace without the kernels, or a
program without the counters, gives nothing to read."""

from chipbench import manifest as mf


def read(ctx):
    return mf.load_by_name("layer_metrics", "moe_experts_roofline").read(ctx)
