"""Device ms per round in the round's FedGradNorm server: the loss
ratios, the last shared layer's masks and masked norms, and the weight
update (``hota.fgn``), less the channel draws inside it. Summed over the
chips; an op's phase is the innermost in its scope path
(``bench/scopes.py``)."""
from bench.scopes import phase_ms_per_round


def read(ctx):
    return phase_ms_per_round(ctx, "fgn")
