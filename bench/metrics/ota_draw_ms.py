"""Device ms per round in the channel's threefry draws: the chunked
streams with their reshape and truncation (``hota.ota_draw`` in
``ota._chunked_stream`` and ``ota.stream_range_bits``), wherever in the
round they are drawn. Summed over the chips; an op's phase is the
innermost in its scope path (``bench/scopes.py``)."""
from bench.scopes import phase_ms_per_round


def read(ctx):
    return phase_ms_per_round(ctx, "ota_draw")
