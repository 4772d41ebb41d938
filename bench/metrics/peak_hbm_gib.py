"""Peak device memory over the run, on the fullest of the cell's devices,
in GiB: ``memory_stats()["peak_bytes_in_use"]``."""


def read(ctx):
    return None if ctx.peak_bytes is None else ctx.peak_bytes / 2 ** 30
