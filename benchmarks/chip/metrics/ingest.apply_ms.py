"""Median host milliseconds of one ``MaintainedScorer.apply``, from the
program's histogram ``ivm.apply_ms`` over the run; nothing where the
program keeps no such histogram.  In a traced run the program waits
for the device before it reads its clock (``obs.fence``), so the time
covers the factor-row writes on the device.  Set-up applies the
traffic's ``warmup`` micro-batches and the window hundreds, so the
median is a window apply.  The histogram's buckets are about 9% wide."""


def read(ctx):
    from repro.obs import get_registry

    reg = get_registry()
    if "ivm.apply_ms" not in reg.names():
        return None
    h = reg.histogram("ivm.apply_ms")
    return h.quantile(0.5) if h.count else None
