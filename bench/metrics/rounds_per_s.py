"""Federated rounds completed in the timed window over its wall time (the
window ends when the final state is ready)."""


def read(ctx):
    return ctx.rounds / ctx.window_s if ctx.window_s > 0 else None
