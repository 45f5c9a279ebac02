"""The least time the Mamba-2 scan with groups of B and C could take (the
larger of its operations over the bf16 peak and its bytes over the HBM
peak, from the configuration's ``flops`` module: the chunked algorithm's
least work at the configuration's chunk, ``C B^T`` and B and C once per
group, x, Δ and y once a pass), over the device time of every
``ssd_scan_`` event (the forward kernel, a region's second forward where
one runs, the backward kernel).  The reading is ``ssd_scan_roofline``'s,
for the cells whose scan reads B and C by group; a trace without the
kernels, or a configuration without a scan, gives nothing to read."""

from chipbench import manifest as mf


def read(ctx):
    return mf.load_by_name("layer_metrics", "ssd_scan_roofline").read(ctx)
