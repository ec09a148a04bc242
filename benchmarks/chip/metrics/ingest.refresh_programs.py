"""Refresh programs the run built, from the program's counter
``ivm.refresh_programs`` (one per path-restricted refresh program
traced, whatever the version of the join tree); nothing where the
program keeps no such counter.  Set-up's warm-up builds the
micro-batches' programs, so a reading above set-up's count (which the
driver prints) is a build inside the window."""


def read(ctx):
    from repro.obs import get_registry

    reg = get_registry()
    if "ivm.refresh_programs" not in reg.names():
        return None
    return float(reg.counter("ivm.refresh_programs").value)
