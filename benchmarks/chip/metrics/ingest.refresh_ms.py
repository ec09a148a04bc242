"""Median host milliseconds of one path-restricted message refresh,
from the program's histogram ``ivm.refresh_ms`` over the run; nothing
where the program keeps no such histogram.  In a traced run the
program waits for the refreshed messages before it reads its clock
(``obs.fence``), so the time covers the refresh program's device time.
The histogram's buckets are about 9% wide."""


def read(ctx):
    from repro.obs import get_registry

    reg = get_registry()
    if "ivm.refresh_ms" not in reg.names():
        return None
    h = reg.histogram("ivm.refresh_ms")
    return h.quantile(0.5) if h.count else None
