"""Device ms per round in the parameter server's update: the slab Adam
step (``hota.ps_update``). Summed over the chips; an op's phase is the
innermost in its scope path (``bench/scopes.py``)."""
from bench.scopes import phase_ms_per_round


def read(ctx):
    return phase_ms_per_round(ctx, "ps_update")
