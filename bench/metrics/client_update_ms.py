"""Device ms per round in the round's client update: the vmapped local
steps of every client (``hota.client_update`` in
``HotaSim.step_with_channel``). Summed over the chips; an op's phase is
the innermost in its scope path (``bench/scopes.py``)."""
from bench.scopes import phase_ms_per_round


def read(ctx):
    return phase_ms_per_round(ctx, "client_update")
