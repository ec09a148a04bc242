"""Peak device memory of the run, ``peak_bytes_in_use``, in GiB."""


def read(ctx):
    peak = ctx.device.get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
