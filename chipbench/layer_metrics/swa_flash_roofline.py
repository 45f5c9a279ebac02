"""The least time the band's and the full layers' attention could take (the
larger of its operations over the bf16 peak and its bytes over the HBM
peak, from the configuration's ``flops`` module: pairs under the band in a
sliding layer and under the diagonal in a full one, K and V at their own
heads; the same work whatever implements it), over the device time of
every ``flash_attention_`` event, the region's second forward among them:
``mla_flash_roofline``'s reading, of this configuration's operations and
bytes."""

from chipbench import manifest as mf

read = mf.load_by_name("layer_metrics", "mla_flash_roofline").read
