"""Shared helpers for ops (activation modes, padding math)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Activation modes, matching reference ActiMode (ffconst.h).
AC_MODE_NONE = "none"
AC_MODE_RELU = "relu"
AC_MODE_SIGMOID = "sigmoid"
AC_MODE_TANH = "tanh"
AC_MODE_GELU = "gelu"
AC_MODE_SILU = "silu"

_ACTIVATIONS = {
    AC_MODE_NONE: lambda x: x,
    AC_MODE_RELU: jax.nn.relu,
    AC_MODE_SIGMOID: jax.nn.sigmoid,
    AC_MODE_TANH: jnp.tanh,
    AC_MODE_GELU: jax.nn.gelu,
    AC_MODE_SILU: jax.nn.silu,
}


def apply_activation(x: jax.Array, mode) -> jax.Array:
    if mode is None or mode is False:
        return x
    if callable(mode):
        return mode(x)
    return _ACTIVATIONS[mode](x)


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """x * rsqrt(mean(x^2) + eps) * w over the last axes `w` spans (one
    for a hidden vector, two for a (heads, head_dim) projection normed
    whole), statistics in f32, result in x's dtype."""
    xf = x.astype(jnp.float32)
    axes = tuple(range(-w.ndim, 0))
    var = jnp.mean(jnp.square(xf), axis=axes, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps)
            * w.astype(jnp.float32)).astype(x.dtype)


def rotary(x: jax.Array, positions: jax.Array, theta: float,
           interleaved: bool = False) -> jax.Array:
    """Rotary position embedding over the whole head: x (..., H, D),
    positions (...) absolute. Half-rotation pairing (x[:D/2] with
    x[D/2:]) or, `interleaved` (GPT-J's), neighbours (x[2i] with
    x[2i+1]); angles position * theta^(-2i/D), computed in f32."""
    d = x.shape[-1]
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = positions.astype(jnp.float32)[..., None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    xf = x.astype(jnp.float32)
    if interleaved:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
        return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                         axis=-1).reshape(x.shape).astype(x.dtype)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def conv_out_dim(in_size: int, kernel: int, stride: int, pad: int) -> int:
    """Output spatial size, matching the reference's conv shape math
    (src/runtime/model.cc:134-212 sub-tensor computation)."""
    return (in_size + 2 * pad - kernel) // stride + 1
