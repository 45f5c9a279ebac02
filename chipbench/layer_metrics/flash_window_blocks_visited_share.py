"""Of the ``block_q x block_k`` blocks that the flash kernels' sites with a
window could visit, the share their sweeps do visit: the program's counter
``flash_blocks_total{kernel, kind, window="true"}``, ``visited`` over
``possible``, over every step the process traced (static in the shapes:
the training step's three kernels and the test-mode forward read alike).
Near the band's share of the square; it rises if a later change stops
skipping what lies outside the band.  A program without the counter, or
without a windowed site, gives nothing to read."""


def read(ctx):
    try:
        from paddle_tpu.observability import metrics
        collected = metrics.registry().collect()
    except (ImportError, AttributeError):
        return None
    totals = {}
    for m in collected:
        labels = dict(m.labels)
        if m.name == "flash_blocks_total" and labels.get("window") == "true":
            totals[labels["kind"]] = totals.get(labels["kind"], 0) + m.value
    if not totals.get("possible"):
        return None
    return 100.0 * totals.get("visited", 0) / totals["possible"]
