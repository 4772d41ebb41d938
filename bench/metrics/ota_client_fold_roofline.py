"""Share of its HBM roofline, in %, that the client-folded aggregation
kernel (``ota_aggregate_client_pallas``) reaches: the least time its HBM
traffic takes at the chip's HBM bandwidth, over its measured device time,
summed over the kernel's calls in the traced window.

The kernel's calls are the ``tpu_custom_call``s whose first operand is
the (C, N, rows, 128) float32 gradient block and which read channel
words (``u32``). Its traffic is computed from the shapes in each call's
HLO: every operand and result, less those the compiler placed in on-chip
memory (layout memory space ``S(1)`` or above), which the kernel does not
read from HBM."""
import re

from bench.metrics.ota_kernel_ms import OTA_KERNELS

FIRST_F32_BLOCK = re.compile(r'custom-call\(f32\[(?:\d+,){3}128\]')
ARRAY = re.compile(r'\b(f32|u32|s32|bf16|f16|s8|u8)\[([\d,]*)\]\{([^}]*)\}')
BYTES = {"f32": 4, "u32": 4, "s32": 4, "bf16": 2, "f16": 2, "s8": 1, "u8": 1}


def is_client_fold(name: str) -> bool:
    return bool(OTA_KERNELS.search(name) and FIRST_F32_BLOCK.search(name))


def hbm_bytes(name: str) -> int:
    """Bytes of a kernel call's results and operands that live in HBM."""
    body = name.split(", custom_call_target=")[0]
    total = 0
    for dtype, dims, layout in ARRAY.findall(body):
        if re.search(r"S\([1-9]", layout):
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * BYTES[dtype]
    return total


def read(ctx):
    t = ctx.trace
    if t is None or ctx.peak is None or not t.rounds:
        return None
    secs = moved = 0.0
    for e in t.ops():
        if is_client_fold(e.name):
            secs += e.dur_ns * 1e-9
            moved += hbm_bytes(e.name)
    if secs <= 0:
        return None
    return 100.0 * moved / ctx.peak["hbm_bytes_per_s"] / secs
