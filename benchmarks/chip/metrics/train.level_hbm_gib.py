"""Largest device footprint of the trainer's level programs, in GiB:
the program's gauge ``train.level_program_bytes`` (temporaries,
arguments and outputs less aliases, from each program's
``memory_analysis`` as the trainer builds it); nothing where the
program keeps no such gauge."""


def read(ctx):
    from repro.obs import get_registry

    reg = get_registry()
    if "train.level_program_bytes" not in reg.names():
        return None
    return reg.gauge("train.level_program_bytes").value / 2 ** 30
