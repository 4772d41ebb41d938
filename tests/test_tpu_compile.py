"""The main-path Pallas kernels compile for a described TPU v5e.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
unlowerable primitives, casts it has no rule for, blocks that break the
(8, 128) tiling rule, VMEM overruns. Each test here lowers one kernel
through its public wrapper for one chip of a described ``v5e:2x2``
topology and compiles it with the TPU compiler — no chip is attached,
nothing runs; a passing compile is not a chip run.

Shapes are the paper MLP's (Table I, C=10 clusters × N=3 clients) at its
largest leaf, ``fc2.w`` (1024 × 2048 = 16384 slab rows of 128 lanes).

The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time, and every pytest worker
imports every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import ota
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.masked_gradnorm.ops import masked_gradnorm
from repro.kernels.ota_channel.kernel import ota_aggregate_client_pallas
from repro.kernels.ota_channel.ops import (
    ota_client_fold_apply, ota_client_fold_drawn_apply, ota_mask_count_apply,
    ota_mask_weight_apply,
)
from repro.kernels.slab import LANE

C, N = 10, 3                      # paper topology
ROWS = 1024 * 2048 // LANE        # largest paper-MLP leaf as a slab
P = ROWS * LANE
FINAL_P = 512 * 256 + 256         # ω̃ (final shared layer) entries


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip cannot be read back without one:
    keep the persistent cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


pytestmark = pytest.mark.usefixtures("no_persistent_cache")


def _compile(fn, one_chip, kernel, *shapes):
    """Lower ``fn`` on the described chip, compile, and check that the
    program holds a Mosaic kernel (not an XLA fallback) under its stable
    name ``kernel``: in the lowered module, and as the name of the
    compiled instruction that a profiler trace shows."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    lowered = jax.jit(fn).lower(*args)
    assert f'kernel_name = "{kernel}"' in lowered.as_text()
    compiled = lowered.compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"^\s*(ROOT )?%{kernel}(\.\d+)? = .*custom-call\(",
                     text, re.M), kernel
    return compiled


F32, U32 = jnp.float32, jnp.uint32


def test_client_fold_compiles(one_chip):
    """The simulator's hot path: eqs. 3 + 8-10 in one kernel per leaf."""
    def fn(g, p, bits, nbits, sig):
        return ota_client_fold_apply(g, p, bits, nbits, sig, 3.2e-2, 1.0,
                                     1.0, N, interpret=False, impl="pallas")
    _compile(fn, one_chip, "ota_client_fold", ((C, N, 1024, 2048), F32),
             ((C, N), F32), ((C, P), U32), ((P,), U32), ((C,), F32))


def test_client_fold_drawn_compiles(one_chip):
    """The fused-mode hot path on the flat view: the kernel computes its
    gain and noise words from their stream positions. A 1-D leaf of
    fc2.w's size takes the (rows, 128) view (starting 2048 words into
    its section, its 16384 rows span 17 chunks of the key table)."""
    word0 = 2048
    n_chunks = (word0 + P - 1) // ota.CHUNK + 1

    def fn(g, p, keys, sig):
        return ota_client_fold_drawn_apply(g, p, keys, word0,
                                           ota.stream_words, sig, 3.2e-2,
                                           1.0, 1.0, N, interpret=False)
    _compile(fn, one_chip, "ota_client_fold_drawn",
             ((C, N, P), F32), ((C, N), F32),
             (((C + 1) * n_chunks * 2,), U32), ((C,), F32))


@pytest.mark.parametrize("shape,word0", [((1024, 2048), 2048),
                                         ((512, 256), 1024),
                                         ((256, 512), 0),
                                         ((512, 1024), 1024),
                                         ((2048, 512), 3072)],
                         ids=["fc2_w", "final_w", "fc0_w", "fc1_w", "fc3_w"])
def test_client_fold_drawn2d_compiles(one_chip, shape, word0):
    """The drawing kernel on each of the paper round's 2-D leaves in its
    own (a, b) layout: fc2.w (column blocks of 1024, draw tiles of 8
    rows), final.w (whole 256-wide rows, tiles of 32), fc0.w and fc3.w
    (512-wide rows, tiles of 16) and fc1.w (1024-wide rows, tiles of
    8)."""
    size = shape[0] * shape[1]
    n_chunks = (word0 + size - 1) // ota.CHUNK + 1

    def fn(g, p, keys, sig):
        return ota_client_fold_drawn_apply(g, p, keys, word0,
                                           ota.stream_words, sig, 3.2e-2,
                                           1.0, 1.0, N, interpret=False)
    _compile(fn, one_chip, "ota_client_fold_drawn2d",
             ((C, N) + shape, F32), ((C, N), F32),
             (((C + 1) * n_chunks * 2,), U32), ((C,), F32))


@pytest.fixture(scope="module")
def fused_round(one_chip, no_persistent_cache):
    """The paper round (``HotaSim._step``, fused words) compiled for the
    described chip, steered onto its TPU path: (module text, the same
    module printed with operand shapes, as a profiler trace names ops)."""
    from jax._src.lib import xla_client
    from repro.common.config import FLConfig, ModelConfig, TrainConfig
    from repro.core.sim import HotaSim
    from repro.kernels.masked_gradnorm import ops as gradnorm_ops
    from repro.kernels.ota_channel import ops as channel_ops
    from repro.models.model import build_model
    # wrappers such as masked_gradnorm are jitted and pick their platform
    # path at trace time: no trace may cross the patch in either direction
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as mp:
        for mod in (ota, channel_ops, gradnorm_ops):
            mp.setattr(mod, "on_tpu", lambda: True)
        sim = HotaSim(build_model(ModelConfig(family="mlp")),
                      FLConfig(n_clusters=C, n_clients=N),
                      TrainConfig(lr=3e-4), [8, 8, 8])

        def spec(tree):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=one_chip), tree)
        state = jax.eval_shape(sim.init, jax.random.PRNGKey(0))
        compiled = HotaSim._step.lower(
            sim, spec(state),
            jax.ShapeDtypeStruct((C, N, 24, 256), F32, sharding=one_chip),
            jax.ShapeDtypeStruct((C, N, 24), jnp.int32, sharding=one_chip),
            jax.ShapeDtypeStruct((2,), U32, sharding=one_chip),
            spec(sim.chan), spec(sim.faults)).compile()
    jax.clear_caches()
    opts = xla_client._xla.HloPrintOptions.short_parsable()
    opts.print_operand_shape = True
    opts.print_metadata = False
    opts.print_backend_config = False
    shaped = compiled.runtime_executable().hlo_modules()[0].to_string(opts)
    return compiled.as_text(), shaped


def test_fused_round_holds_no_chunk_words(fused_round):
    """No array the round keeps in memory is a chunked draw: every u32
    array outside a fusion body is smaller than C × CHUNK words (the old
    draw kept u32[C, 17·CHUNK] and its pads and copies)."""
    from repro.launch.hlo_cost import parse_hlo
    text, _ = fused_round
    assert re.search(r"%ota_client_fold_drawn(\.\d+)? = .*custom-call\(",
                     text)
    assert not re.search(r"%ota_client_fold(\.\d+)? = ", text)
    comps, _ = parse_hlo(text)
    fused = {m for comp in comps.values() for op in comp.ops
             if op.opcode == "fusion"
             for m in re.findall(r"calls=%?([\w.\-]+)", op.attrs)}
    big = sorted({
        (op.name, shape) for name, comp in comps.items() if name not in fused
        for op in comp.ops for dtype, shape in op.result_shapes
        if dtype == "u32" and np.prod(shape) >= C * ota.CHUNK})
    assert not big, big


def test_roofline_reader_finds_the_drawing_kernel(fused_round):
    """``ota_client_fold_drawn_roofline`` reads the kernel by its name and
    a u32 operand (the key table) among its operands. Each drawing-kernel
    call of the round must match: the flat-view calls (fc1.b, fc2.b) and
    the 2-D leaves' calls (``ota_client_fold_drawn2d``, operand (C, N, a,
    b)); the HBM bytes it counts hold the largest leaf's gradients.
    ``ota_client_fold_roofline``'s shape match, a (d, d, d, 128) first
    operand, sees the two flat-view calls alone."""
    from bench.metrics.ota_client_fold_drawn_roofline import (
        hbm_bytes, is_drawn,
    )
    from bench.metrics.ota_client_fold_roofline import is_client_fold
    _, shaped = fused_round
    call = re.compile(r"\s*(ROOT )?%?ota_client_fold_drawn(2d)?(\.\d+)? = ")
    calls = [line for line in shaped.splitlines() if call.match(line)]
    assert len(calls) == 7           # the paper MLP's leaves of >= 1024
    assert all(is_drawn(line) for line in calls)
    flat = [line for line in calls if is_client_fold(line)]
    assert len(flat) == 2
    assert all("ota_client_fold_drawn2d" not in line for line in flat)
    # at least fc2.w's (C, N, 1024, 2048) f32 gradients come from HBM
    assert sum(hbm_bytes(line) for line in calls) >= 4 * C * N * P


def test_fused_round_reads_gradients_in_place(fused_round):
    """The five 2-D leaves reach the drawing kernel as (C, N, a, b): no op
    of the round makes their flat (C, N, rows, 128) view (fc2.w 16384
    rows, fc3.w 8192, fc1.w 4096, fc0.w and final.w 1024)."""
    text, shaped = fused_round
    views = re.findall(r"= f32\[10,3,(?:16384|8192|4096|1024),128\]", text)
    assert not views, views
    operands = re.findall(
        r"%?ota_client_fold_drawn2d(?:\.\d+)? = .*?custom-call\("
        r"f32\[10,3,(\d+),(\d+)\]", shaped)
    assert sorted(operands) == sorted([
        ("1024", "2048"), ("2048", "512"), ("512", "1024"), ("256", "512"),
        ("512", "256")]), operands


def test_client_fold_cluster_blocked_compiles(one_chip):
    """The C-blocked variant (auto-selected once C·(N+1) outgrows VMEM):
    its per-block params row must satisfy the block tiling rule."""
    def fn(x, bits, nbits, params):
        return ota_aggregate_client_pallas(x, bits, nbits, params,
                                           n_clients=N, interpret=False,
                                           cluster_block=2)
    _compile(fn, one_chip, "ota_client_fold_cblk",
             ((C, N, ROWS, LANE), F32), ((C, ROWS, LANE), U32),
             ((ROWS, LANE), U32), ((1, C * (N + 2) + 3), F32))


def test_mask_weight_compiles(one_chip):
    """The distributed backward's per-leaf w·g·M kernel."""
    def fn(x, bits, w):
        return ota_mask_weight_apply(x, bits, 0.7, 3.2e-2, 1.0, w,
                                     interpret=False, impl="pallas")
    _compile(fn, one_chip, "ota_mask_weight", ((1024, 2048), F32),
             ((P,), U32), ((), F32))


def test_mask_count_compiles(one_chip):
    """The distributed backward's collective-free |M| count kernel."""
    def fn(x, bits_all, sig, w):
        return ota_mask_count_apply(x, bits_all, 1, sig, 3.2e-2, 1.0, w,
                                    interpret=False, impl="pallas")
    _compile(fn, one_chip, "ota_mask_count", ((1024, 2048), F32),
             ((C, P), U32), ((C,), F32), ((), F32))


def test_masked_gradnorm_compiles(one_chip):
    """eq. 6's masked ω̃ norms for one cluster's N clients."""
    def fn(g, m):
        return masked_gradnorm(g, m, interpret=False, impl="pallas")
    _compile(fn, one_chip, "masked_gradnorm", ((N, FINAL_P), F32),
             ((FINAL_P,), F32))


def test_flash_attention_compiles(one_chip):
    """Causal flash attention at StableLM-3B's head layout (32 × 80)."""
    def fn(q, k, v):
        return flash_attention(q, k, v, interpret=False)
    shape = ((1, 2048, 32, 80), jnp.bfloat16)
    _compile(fn, one_chip, "flash_attention", shape, shape, shape)
