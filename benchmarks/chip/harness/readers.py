"""Small helpers the per-layer metric readers share."""
from __future__ import annotations


def idle_pct(ctx):
    """Share of the traced window in which no operation ran on the
    device, in percent; nothing without a device trace."""
    tr = ctx.trace
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def mean_ms(ctx, span: str):
    d = ctx.spans.durations(span)
    return 1e3 * sum(d) / len(d) if d else None
