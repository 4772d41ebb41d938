"""Host ms per round spent placing the round's batch and key on the
device and dispatching the step: the ``bench.input`` spans of the traced
window over its rounds."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.rounds:
        return None
    return ctx.trace.span_seconds("bench.input") / ctx.trace.rounds * 1e3
