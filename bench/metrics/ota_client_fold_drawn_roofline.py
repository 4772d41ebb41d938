"""Share of its HBM roofline, in %, that the drawing client-fold kernel
reaches: the least time its HBM traffic takes at the chip's HBM
bandwidth, over its measured device time, summed over every call of it
in the traced window.

The calls are the ``pallas_call``s named ``ota_client_fold_drawn`` (a
leaf's flat (rows, 128) view) and ``ota_client_fold_drawn2d`` (a 2-D leaf
in its own (a, b) layout) in ``kernels/ota_channel``, found by their
instruction name and by the ``u32`` operand every channel kernel takes
(here the chunk-key table). ``ota_client_fold_roofline`` selects the
kernel by a (C, N, rows, 128) first operand instead, so it misses the
2-D calls. Their traffic is counted from the shapes in each call's HLO
as ``ota_client_fold_roofline`` counts it, which does not depend on the
leaf's layout. A program without the drawing kernel gives no reading."""
import re

from bench.metrics.ota_client_fold_roofline import hbm_bytes
from bench.metrics.ota_kernel_ms import OTA_KERNELS

DRAWN = re.compile(
    r'^\s*(?:ROOT )?%?ota_client_fold_drawn(?:2d)?(?:\.\d+)? = ')


def is_drawn(name: str) -> bool:
    return bool(DRAWN.match(name) and OTA_KERNELS.search(name))


def read(ctx):
    t = ctx.trace
    if t is None or ctx.peak is None or not t.rounds:
        return None
    secs = moved = 0.0
    for e in t.ops():
        if is_drawn(e.name):
            secs += e.dur_ns * 1e-9
            moved += hbm_bytes(e.name)
    if secs <= 0:
        return None
    return 100.0 * moved / ctx.peak["hbm_bytes_per_s"] / secs
