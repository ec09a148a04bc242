"""Median host milliseconds to build one scorer program
(``serve.scorer_build``: trace, lower, and compile or load from the
cache), from the program's histogram ``serve.scorer_build_ms`` over the
run; nothing where the program keeps no such histogram.  Set-up builds
the traffic's ``warmup`` programs, the first a compile for a seed new
to the cache, and the window one per rescore, so the median is a
window build.  The histogram's buckets are about 9% wide."""


def read(ctx):
    from repro.obs import get_registry

    reg = get_registry()
    if "serve.scorer_build_ms" not in reg.names():
        return None
    h = reg.histogram("serve.scorer_build_ms")
    return h.quantile(0.5) if h.count else None
