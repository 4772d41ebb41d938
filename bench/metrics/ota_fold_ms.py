"""Device ms per round in the over-the-air aggregation: the transmit
weights, the per-leaf slices of the streams, the gradients' relayout and
the client-fold kernels (``hota.ota_fold``), less the channel draws
inside it. Summed over the chips; an op's phase is the innermost in its
scope path (``bench/scopes.py``)."""
from bench.scopes import phase_ms_per_round


def read(ctx):
    return phase_ms_per_round(ctx, "ota_fold")
