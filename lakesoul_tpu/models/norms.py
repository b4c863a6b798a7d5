"""The norms the causal-LM families and the attention operands share (plain
``jnp``: no kernel, no family): the RMS norm, and LayerNorm with weight and
bias."""

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps, *, centred: bool = True):
    """``x * rsqrt(mean(x^2) + eps) * (1 + w)`` in float32 (``* w`` where the
    weight is not zero-centred); float32 out."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y * ((1.0 + w) if centred else w)


def layer_norm(x, w, b, eps):
    """``(x - mean(x)) * rsqrt(var(x) + eps) * w + b`` in float32; float32
    out."""
    x32 = x.astype(jnp.float32)
    centred = x32 - jnp.mean(x32, axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(jnp.mean(centred * centred, axis=-1, keepdims=True) + eps) * w + b
