"""Share of the scorer programs the run's ensembles took that had been
built before, in percent: 100 · ``serve.scorer_hits`` / (hits +
``serve.scorer_programs``) over the run, from the program's counters;
nothing where the program keeps no ``serve.scorer_hits`` counter.
Set-up builds the one program the cell's ensembles share and every later
ensemble takes it, so a reading well below 100% is a build per ensemble."""


def read(ctx):
    from repro.obs import get_registry

    reg = get_registry()
    if "serve.scorer_hits" not in reg.names():
        return None
    hits = reg.counter("serve.scorer_hits").value
    built = reg.counter("serve.scorer_programs").value
    return 100.0 * hits / (hits + built) if hits + built else None
