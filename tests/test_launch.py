"""Launch-layer units: input specs, microbatch picker, mesh construction."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import INPUT_SHAPES
from repro.configs import get_config
from repro.launch.dryrun import _pick_microbatches, active_params
from repro.launch.steps import input_specs


def test_input_specs_train():
    cfg = get_config("stablelm_3b")
    ins = input_specs(cfg, INPUT_SHAPES["train_4k"])
    assert ins["tokens"].shape == (256, 4096)
    assert ins["tokens"].dtype == jnp.int32
    assert ins["labels"].shape == (256, 4096)


def test_input_specs_vlm_embeds():
    cfg = get_config("phi3_vision_4_2b")
    ins = input_specs(cfg, INPUT_SHAPES["prefill_32k"])
    # stubbed vision frontend supplies patch EMBEDDINGS, not token ids
    assert ins["tokens"].shape == (32, 32768, cfg.d_model)
    assert ins["tokens"].dtype == jnp.bfloat16


def test_input_specs_audio_tokens():
    cfg = get_config("musicgen_medium")
    ins = input_specs(cfg, INPUT_SHAPES["train_4k"])
    # EnCodec codes are discrete tokens
    assert ins["tokens"].dtype == jnp.int32


def test_input_specs_decode():
    cfg = get_config("qwen2_5_14b")
    ins = input_specs(cfg, INPUT_SHAPES["decode_32k"])
    assert ins["tokens"].shape == (128, 1)
    assert ins["positions"].shape == (128,)


def test_pick_microbatches_scales_with_model():
    small = get_config("musicgen_medium")
    big = get_config("mixtral_8x22b")
    shape = INPUT_SHAPES["train_4k"]
    assert _pick_microbatches(big, shape, 16) >= _pick_microbatches(small, shape, 16)
    assert _pick_microbatches(small, shape, 16) >= 1


def test_active_params_moe_discount():
    mix = get_config("mixtral_8x22b")
    full = active_params(mix.replace(moe=None))
    act = active_params(mix)
    assert act < 0.5 * 141e9          # top-2 of 8 experts ≈ 39B active
    assert act > 20e9


def test_dryrun_import_leaves_xla_flags():
    """Importing the dry-run module (done above) forces no host devices;
    only running it as a script does."""
    import os
    assert "device_count=512" not in os.environ.get("XLA_FLAGS", "")


def test_train_main_one_device_mesh(monkeypatch, capsys):
    """The training CLI on a 1,1,1 mesh with an explicit layout: every
    round logs, and the returned metrics are finite."""
    from repro.launch import train
    monkeypatch.setattr(train, "enable_compile_cache", lambda: None)
    m = train.main(["--mesh", "1,1,1", "--steps", "2", "--no-tune-layout",
                    "--log-every", "1", "--seq-len", "16",
                    "--batch-per-client", "2"])
    assert np.isfinite(m["loss"])
    out = capsys.readouterr().out
    assert "step    0" in out and "step    1" in out


def test_train_main_names_devices_on_short_mesh(monkeypatch):
    from repro.launch import train
    monkeypatch.setattr(train, "enable_compile_cache", lambda: None)
    with pytest.raises(SystemExit, match="needs 1024 devices; found"):
        train.main(["--mesh", "32,32,1", "--steps", "1"])


def test_long500k_skip_flags():
    skip = ["stablelm_3b", "musicgen_medium", "phi3_vision_4_2b",
            "phi3_5_moe_42b", "qwen2_5_14b"]
    run = ["starcoder2_3b", "gemma3_12b", "zamba2_1_2b", "xlstm_1_3b",
           "mixtral_8x22b"]
    for a in skip:
        assert not get_config(a).is_subquadratic, a
    for a in run:
        assert get_config(a).is_subquadratic, a
