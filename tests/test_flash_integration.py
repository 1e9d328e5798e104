"""Flash-prefill glue in llama.apply exercised on CPU (interpret mode):
the full model with use_flash_prefill must match the masked XLA path."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubeai_tpu.models import llama
from kubeai_tpu.models.base import ModelConfig
from kubeai_tpu.ops import flash_attention as flash_ops

CFG = ModelConfig(
    vocab_size=256, hidden_size=64, intermediate_size=128, num_layers=2,
    num_heads=4, num_kv_heads=2, dtype="float32", max_position=1024,
)


@pytest.fixture(autouse=True)
def interpret_flash_kernel(monkeypatch):
    """The serving path always compiles the kernel for the chip; on the
    CPU the test steers it into Pallas interpret mode."""
    monkeypatch.setattr(
        flash_ops, "flash_attention_tpu",
        functools.partial(flash_ops.flash_attention_tpu, interpret=True),
    )


def test_flash_prefill_matches_masked_path():
    params = llama.init_params(CFG, jax.random.key(0))
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 256, (2, 256)))
    lengths = jnp.asarray([256, 200], jnp.int32)

    ref_logits, ref_cache = llama.prefill(
        params, CFG, tokens, llama.init_cache(CFG, 2, 512), lengths
    )
    flash_cfg = CFG.replace(use_flash_prefill=True)
    got_logits, got_cache = llama.prefill(
        params, flash_cfg, tokens, llama.init_cache(CFG, 2, 512), lengths
    )
    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(ref_logits), rtol=2e-4, atol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(got_cache["k"]), np.asarray(ref_cache["k"]), rtol=1e-5, atol=1e-5
    )

    # Decode continues identically from a flash-prefilled cache.
    nxt = jnp.argmax(got_logits[:, -1], -1)[:, None].astype(jnp.int32)
    ref_step, _ = llama.decode_step(params, CFG, nxt, ref_cache, lengths)
    got_step, _ = llama.decode_step(params, flash_cfg, nxt, got_cache, lengths)
    np.testing.assert_allclose(
        np.asarray(got_step), np.asarray(ref_step), rtol=2e-4, atol=2e-4
    )


def test_flash_gate_skips_offset_positions():
    """apply() with non-arange positions must NOT take the flash path even
    when shapes qualify (left_aligned=False default)."""
    params = llama.init_params(CFG, jax.random.key(0))
    cache = llama.init_cache(CFG, 1, 512)
    tokens = jnp.asarray(np.random.default_rng(1).integers(0, 256, (1, 256)))
    offset_pos = jnp.arange(100, 356, dtype=jnp.int32)[None, :]
    flash_cfg = CFG.replace(use_flash_prefill=True)
    # Would be mis-masked by the flash kernel; the gate must route it to
    # the masked path and produce the same result as the plain config.
    got, _ = llama.apply(params, flash_cfg, tokens, offset_pos, cache)
    ref, _ = llama.apply(params, CFG, tokens, offset_pos, cache)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), rtol=1e-6, atol=1e-6)
