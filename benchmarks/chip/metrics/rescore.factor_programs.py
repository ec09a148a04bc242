"""Factor programs the run built, from the program's counter
``serve.factor_programs`` (one per factor program traced, whatever the
ensemble or the data); nothing where the program keeps no such counter.
Set-up builds the rescore's one program, so a reading above 1 is a
build inside the window."""


def read(ctx):
    from repro.obs import get_registry

    reg = get_registry()
    if "serve.factor_programs" not in reg.names():
        return None
    return float(reg.counter("serve.factor_programs").value)
