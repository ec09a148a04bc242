"""Share of the bandwidth roofline a fresh-scored micro-batch reaches:
the bytes the answer has to move (``harness.ingest_counts``, the mean
over the window's micro-batches) at the device's HBM bandwidth
(``peaks.json``), over the device-busy seconds per micro-batch in the
traced window, in percent."""


def read(ctx):
    tr, n = ctx.trace, ctx.extra.get("micro_batches")
    if not tr or not n or tr["busy_s"] <= 0:
        return None
    peak = ctx.peaks["devices"][ctx.device["kind"]]["hbm_bytes_per_s"]
    return 100.0 * (ctx.extra["bytes"] / peak) / (tr["busy_s"] / n)
