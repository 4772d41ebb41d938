"""Seconds from process start to the first timed round: imports, batches,
weights, compiling or loading the round from the cache, checked rounds."""


def read(ctx):
    return ctx.setup_s
