"""Share of the bandwidth roofline a rescore reaches: the bytes the
answer has to move (``harness.counts.rescore_bytes``) at the device's
HBM bandwidth (``peaks.json``), over the device-busy seconds per
rescore in the traced window, in percent."""


def read(ctx):
    tr, n = ctx.trace, ctx.extra.get("rescores")
    if not tr or not n or tr["busy_s"] <= 0:
        return None
    peak = ctx.peaks["devices"][ctx.device["kind"]]["hbm_bytes_per_s"]
    return 100.0 * (ctx.extra["bytes"] / peak) / (tr["busy_s"] / n)
