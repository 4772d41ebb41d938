"""Device ms per round in the over-the-air channel's Pallas kernels
(``kernels/ota_channel``), summed over the kernels and the chips.

On the chip a Pallas kernel shows in the trace as a ``tpu_custom_call``
named after the jitted function that holds it; the channel's kernels are
the ones that read channel words (a ``u32`` operand)."""
import re

OTA_KERNELS = re.compile(
    r'custom-call\(.*\bu32\[.*custom_call_target="tpu_custom_call"')


def read(ctx):
    t = ctx.trace
    if t is None or not t.rounds:
        return None
    secs = sum(t.op_seconds(OTA_KERNELS).values())
    return secs / t.rounds * 1e3 if secs > 0 else None
