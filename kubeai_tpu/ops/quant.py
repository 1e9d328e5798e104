"""Weight-only int8 quantization.

Memory/bandwidth play for single-chip serving: an 8B-parameter model is
16 GB in bf16 — over a v5e chip's HBM — but 8 GB in int8 with per-channel
scales. Weights are stored int8 and dequantized at the matmul (XLA fuses
the convert+scale into the dot's operand read, so HBM traffic is the
int8 bytes). Symmetric per-output-channel scaling keeps `x @ W` exact up
to rounding: (x @ q) * s == x @ (q * s).
"""

from __future__ import annotations

from typing import Any

import jax.numpy as jnp
import numpy as np

QKEY, SKEY = "int8_q", "int8_s"


def is_quantized(w: Any) -> bool:
    return isinstance(w, dict) and QKEY in w


def quantize(w, contract_axis: int = -2) -> dict[str, Any]:
    """Symmetric int8 with the absmax reduced ONLY over *contract_axis*
    (the dim a matmul sums over), so scales stay per-output-channel and —
    for layer-stacked weights [L, in, out] — per-layer.

    numpy inputs are quantized ON HOST with numpy outputs: the checkpoint
    loader quantizes before any device transfer, so an 8B model never
    materializes at full precision in HBM."""
    if isinstance(w, np.ndarray):
        # The same arithmetic as below, in place on ONE float32 copy: the
        # loader runs this on several 68M-element layers at once, and
        # five full-size temporaries each is what filled a 40 GiB host.
        w32 = w.astype(np.float32)
        amax = np.maximum(
            w32.max(axis=contract_axis, keepdims=True),
            -w32.min(axis=contract_axis, keepdims=True),
        )
        scale = np.maximum(amax / np.float32(127.0), np.float32(1e-12))
        np.divide(w32, scale, out=w32)
        np.round(w32, out=w32)
        np.clip(w32, -127, 127, out=w32)
        return {QKEY: w32.astype(np.int8), SKEY: scale}
    w32 = jnp.asarray(w).astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=contract_axis, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-12)
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return {QKEY: q, SKEY: scale.astype(jnp.float32)}


def quantize_rows(w) -> dict[str, Any]:
    """Per-row scales (embedding tables: lookups scale row-wise)."""
    return quantize(w, contract_axis=-1)


def dequantize(w: dict[str, jnp.ndarray], dtype=jnp.float32) -> jnp.ndarray:
    return (w[QKEY].astype(jnp.float32) * w[SKEY]).astype(dtype)


def qdot(x: jnp.ndarray, w) -> jnp.ndarray:
    """x @ w for plain or quantized weights. Quantized scales have shape
    [..., 1, out] (keepdims over the contracted dim); the matmul result
    gets the squeezed scale broadcast over output channels."""
    if not is_quantized(w):
        return x @ w
    y = x @ w[QKEY].astype(x.dtype)
    return y * jnp.squeeze(w[SKEY], axis=-2).astype(x.dtype)


def qgather(w, idx, dtype) -> jnp.ndarray:
    """Row-gather (embedding lookup) for plain or per-row-quantized tables."""
    if not is_quantized(w):
        return w.astype(dtype)[idx]
    return (w[QKEY][idx].astype(jnp.float32) * w[SKEY][idx]).astype(dtype)


def qmatT(x: jnp.ndarray, w) -> jnp.ndarray:
    """x @ w.T for plain or per-row-quantized tables (tied lm_head: the
    embedding's rows become output channels)."""
    if not is_quantized(w):
        return x @ w.astype(x.dtype).T
    y = x @ w[QKEY].astype(x.dtype).T
    return y * jnp.squeeze(w[SKEY], axis=-1).astype(x.dtype)
