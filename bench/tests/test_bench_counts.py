"""The yardstick's FLOP and byte counts against counts made by hand."""
import json
import os

import pytest

from bench import harness
from bench.tests.conftest import ROOT


def _paper():
    spec = harness.cell_spec("paper_mlp.round_c10n3")
    return spec["cfg"], spec["traffic"], harness.module("configs",
                                                        "paper_mlp")


def test_paper_mlp_model_flops():
    cfg, traffic, mod = _paper()
    # multiply-adds per row: trunk 256*512 + 512*1024 + 1024*2048 +
    # 2048*512 = 3,801,088; last shared layer 512*256 = 131,072; head
    # 256*8 = 2,048. Forward: 2 * 3,934,208 = 7,868,416 FLOPs per row.
    fwd = 7_868_416
    head_step = fwd + 2 * 2_048                      # + head weight grad
    # shared step: forward, head input grad, final weight + input grad,
    # trunk weight + input grads, less fc0's input grad
    shared_step = fwd + 2 * 2_048 + 4 * 131_072 + 4 * 3_801_088 - 2 * 131_072
    assert head_step == 7_872_512 and shared_step == 23_339_008
    per_round = 30 * 24 * (head_step + shared_step)
    assert per_round == 22_472_294_400
    assert mod.model_flops_per_round(cfg, traffic) == per_round


@pytest.mark.parametrize("tau", [(1, 1), (2, 3)])
def test_paper_mlp_flops_scale_with_local_steps(tau):
    cfg, traffic, mod = _paper()
    cfg = dict(cfg, fl=dict(cfg["fl"], tau_h=tau[0], tau_w=tau[1]))
    per_row = tau[0] * 7_872_512 + tau[1] * 23_339_008
    assert mod.model_flops_per_round(cfg, traffic) == 30 * 24 * per_row


def test_paper_mlp_shapes_match_the_table():
    cfg, traffic, mod = _paper()
    import jax
    w = jax.eval_shape(lambda k: mod.init_weights(cfg, k, 10, 3),
                       jax.random.PRNGKey(0))
    sizes = [l.size for l in jax.tree.leaves(w["omega"])]
    assert sum(sizes) == 3_936_512             # Table I shared params
    assert len(sizes) == 10
    assert w["heads"]["w"].shape == (10, 3, 256, 8)


# one client-fold call of the paper round, as the v5e trace names it
FOLD = ('%_step.12 = f32[16384,128]{1,0:T(8,128)} custom-call('
        'f32[10,3,16384,128]{3,2,1,0:T(8,128)} %reshape.148, '
        'u32[10,16384,128]{2,1,0:T(8,128)S(1)} %slice.241, '
        'u32[16384,128]{1,0:T(8,128)} %slice.215, '
        'f32[1,53]{1,0:T(1,128)S(1)} %bitcast.1264), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{f32[10,3,16384,128]{3,2,1,0}, u32[10,16384,128]{2,1,0}}')
NORM = ('%vmap_jit_masked_gradnorm__.1 = f32[10,3,128]{2,1,0} custom-call('
        'f32[10,3,132096]{2,1,0:T(4,128)S(1)} %select_maximum_fusion, '
        'f32[10,1,132096]{2,1,0:T(1,128)S(1)} %broadcast_in_dim.106), '
        'custom_call_target="tpu_custom_call"')


def test_client_fold_bytes_by_hand():
    rl = harness.module("metrics", "ota_client_fold_roofline")
    assert rl.is_client_fold(FOLD) and not rl.is_client_fold(NORM)
    # in HBM: the (10, 3, 16384, 128) f32 gradients, the (16384, 128)
    # noise words and the (16384, 128) f32 result; the gain words and
    # the params row sit in on-chip memory (S(1)) and are not counted
    grads = 10 * 3 * 16384 * 128 * 4
    assert rl.hbm_bytes(FOLD) == grads + 2 * 16384 * 128 * 4 == 268_435_456
    ota = harness.module("metrics", "ota_kernel_ms").OTA_KERNELS
    assert ota.search(FOLD) and not ota.search(NORM)


def test_peak_table_keyed_by_device_kind():
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["hbm_bytes"] == 16 * 2 ** 30
    assert v5e["ici_bits_per_s"] == 1.6e12
