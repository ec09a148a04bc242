"""Mean milliseconds of ``compile_ensemble`` in the traced window,
ended by ``block_until_ready`` on the stacked factors."""
from harness.readers import mean_ms


def read(ctx):
    return mean_ms(ctx, "bench.factor")
