"""RMSNorm (the Llama-family norm) and LayerNorm.

Computed in float32 regardless of input dtype (bf16 accumulation of squares
loses precision at d_model >= 4096), cast back on output. XLA fuses this into
neighboring ops; a pallas kernel buys nothing here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(
    x: jnp.ndarray, weight: jnp.ndarray, eps: float = 1e-5, plus_one: bool = False
) -> jnp.ndarray:
    """``plus_one`` scales by (1 + weight) — the Gemma convention, whose norm
    weights are zero-initialized deltas around an implicit unit scale."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    variance = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    normed = x32 * jnp.reciprocal(jnp.sqrt(variance + eps))
    w32 = weight.astype(jnp.float32)
    if plus_one:
        w32 = w32 + 1.0
    return (normed * w32).astype(dtype)


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray, eps: float) -> jnp.ndarray:
    """LayerNorm with weight and bias, statistics in float32."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    centred = x32 - mean
    var = jnp.mean(centred * centred, axis=-1, keepdims=True)
    normed = centred * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(x.dtype)
