"""Mean milliseconds of ``score_grouped`` in the traced window, ended by
``block_until_ready``."""
from harness.readers import mean_ms


def read(ctx):
    return mean_ms(ctx, "bench.score")
