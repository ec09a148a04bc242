"""Device idle share of the traced training window, from the profiler
trace: 100 * (1 - busy / window)."""
from harness.readers import idle_pct


def read(ctx):
    return idle_pct(ctx)
