"""Mean of the trainer's ``train.level_ms`` histogram over the traced
window, where tracing makes each level block (``obs.fence``), so the
timer covers the level program's device time."""


def read(ctx):
    return ctx.extra.get("level_ms")
