"""``ota_client_fold_drawn_roofline``: every call of the drawing
client-fold kernel, on the flat view or on a 2-D leaf in its own layout,
found by name, and their HBM bytes counted from the shapes, against
counts made by hand."""
import gzip
import json
import os

import pytest

from bench import harness, trace
from bench.trace import Event

DATA = os.path.join(os.path.dirname(__file__), "data")
DEV0 = "/device:TPU:0"

# fc2.w's call as a v5e trace names it: the (10, 3, 1024, 2048) gradients
# are read from HBM; the key table, the params row and the result sit in
# on-chip memory (S(1))
FC2_W = ('%ota_client_fold_drawn2d.7 = f32[1024,2048]{1,0:T(8,128)S(1)} '
         'custom-call(f32[10,3,1024,2048]{3,2,1,0:T(8,128)} %fusion.77, '
         'u32[374]{0:T(512)S(1)} %reshape.104, '
         'f32[1,53]{1,0:T(1,128)S(1)} %bitcast.905), '
         'custom_call_target="tpu_custom_call", operand_layout_constraints='
         '{f32[10,3,1024,2048]{3,2,1,0}, u32[374]{0}, f32[1,53]{1,0}}')
# fc3.w's, with its result in HBM
FC3_W = ('%ota_client_fold_drawn2d.8 = f32[2048,512]{1,0:T(8,128)} '
         'custom-call(f32[10,3,2048,512]{3,2,1,0:T(8,128)} %fusion.61, '
         'u32[198]{0:T(256)S(1)} %reshape.110, '
         'f32[1,53]{1,0:T(1,128)S(1)} %bitcast.904), '
         'custom_call_target="tpu_custom_call"')
# fc2.b's call on the flat view: every operand on chip
FC2_B = ('%ota_client_fold_drawn.3 = f32[16,128]{1,0:T(8,128)S(1)} '
         'custom-call(f32[10,3,16,128]{3,2,1,0:T(8,128)S(1)} %reshape.102, '
         'u32[22]{0:T(128)S(1)} %reshape.97, '
         'f32[1,53]{1,0:T(1,128)S(1)} %bitcast.906), '
         'custom_call_target="tpu_custom_call"')
# fc2.w's call on the flat view, as the kernel took it before it read
# 2-D leaves in their own layout
FC2_W_FLAT = ('%ota_client_fold_drawn.11 = f32[16384,128]{1,0:T(8,128)} '
              'custom-call(f32[10,3,16384,128]{3,2,1,0:T(8,128)} '
              '%reshape.116, u32[374]{0:T(512)S(1)} %reshape.111, '
              'f32[1,53]{1,0:T(1,128)S(1)} %bitcast.905), '
              'custom_call_target="tpu_custom_call"')
# the client fold on supplied words: another kernel
SUPPLIED = ('%ota_client_fold.3 = f32[16384,128]{1,0:T(8,128)} '
            'custom-call(f32[10,3,16384,128]{3,2,1,0:T(8,128)} %p0, '
            'u32[10,16384,128]{2,1,0:T(8,128)} %p1, '
            'u32[16384,128]{1,0:T(8,128)} %p2), '
            'custom_call_target="tpu_custom_call"')
# a call whose name begins like the kernel's but takes no channel words
NOT_OTA = ('%ota_client_fold_drawn2d.9 = f32[8,128]{1,0:T(8,128)} '
           'custom-call(f32[8,128]{1,0:T(8,128)} %p), '
           'custom_call_target="tpu_custom_call"')
FC2_W_BYTES = 10 * 3 * 1024 * 2048 * 4
FC3_W_BYTES = 10 * 3 * 2048 * 512 * 4 + 2048 * 512 * 4


def _reader():
    return harness.module("metrics", "ota_client_fold_drawn_roofline")


def test_drawing_calls_by_name():
    rl = _reader()
    flat = harness.module("metrics", "ota_client_fold_roofline")
    for call in (FC2_W, FC3_W, FC2_B, FC2_W_FLAT):
        assert rl.is_drawn(call), call
    assert not rl.is_drawn(SUPPLIED) and not rl.is_drawn(NOT_OTA)
    # the shape match sees the flat view only
    assert flat.is_client_fold(FC2_B) and flat.is_client_fold(FC2_W_FLAT)
    assert not flat.is_client_fold(FC2_W) and not flat.is_client_fold(FC3_W)


def test_drawing_bytes_by_hand():
    rl = _reader()
    assert rl.hbm_bytes(FC2_W) == FC2_W_BYTES == 251_658_240
    assert rl.hbm_bytes(FC3_W) == FC3_W_BYTES == 130_023_424
    assert rl.hbm_bytes(FC2_B) == 0
    # the same gradients in either layout are the same bytes
    assert rl.hbm_bytes(FC2_W_FLAT) == FC2_W_BYTES + 16384 * 128 * 4


def _view(ops):
    ev = [Event("/host:CPU", "python", "bench.round", 100.0 * i, 100.0)
          for i in range(2)]
    ev += [Event(DEV0, trace.OPS_LINE, name, float(start), float(dur))
           for name, start, dur in ops]
    return trace.TraceView(ev)


def test_drawing_roofline_over_its_calls():
    # durations in ns: the flat call's time counts with no HBM bytes; the
    # supplied-words call is not this kernel's
    view = _view([(FC2_W, 10, 40), (FC3_W, 60, 20), (FC2_B, 90, 5),
                  (FC2_W, 110, 40), (FC3_W, 160, 20), (SUPPLIED, 185, 9)])
    peak = harness.peak_of("TPU v5 lite")
    ctx = harness.Context(trace=view, peak=peak)
    want = (100.0 * 2 * (FC2_W_BYTES + FC3_W_BYTES)
            / peak["hbm_bytes_per_s"] / (125e-9))
    assert _reader().read(ctx) == pytest.approx(want)


def test_drawing_roofline_reads_the_flat_kernel_alone():
    """On a program whose drawing kernel takes every leaf as its flat
    view, the metric reads what ``ota_client_fold_roofline`` reads."""
    view = _view([(FC2_W_FLAT, 10, 400), (FC2_B, 500, 5)])
    ctx = harness.Context(trace=view, peak=harness.peak_of("TPU v5 lite"))
    flat = harness.module("metrics", "ota_client_fold_roofline")
    assert _reader().read(ctx) == pytest.approx(flat.read(ctx))


@pytest.mark.parametrize("recorded", ["paper_mlp_round_v5e.json.gz",
                                      "paper_mlp_round_scoped_v5e.json.gz"])
def test_drawing_roofline_reads_nothing_without_the_kernel(recorded):
    """Rounds recorded on a v5e before the kernel drew its own words have
    no such call: no reading, and no error."""
    with gzip.open(os.path.join(DATA, recorded), "rt") as f:
        # the scoped trace's rows carry each op's program and scope after
        # the event's five fields
        view = trace.TraceView([Event(*row[:5]) for row in json.load(f)])
    ctx = harness.Context(trace=view, peak=harness.peak_of("TPU v5 lite"))
    assert _reader().read(ctx) is None
    assert harness.module("metrics", "ota_client_fold_roofline").read(ctx)
    assert _reader().read(harness.Context(trace=None, peak=None)) is None
