"""Parameter initializers.

Reference: src/runtime/initializer.cc + initializer_kernel.cu (curand-based
Glorot/Zero/Constant/Uniform/Norm tasks launched per parameter,
initializer.cc:16-330). Here each is a pure function of a PRNG key; the
executor folds a per-parameter key out of the model seed, so results are
reproducible and device-count independent.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def _fans(shape: Tuple[int, ...]) -> Tuple[int, int]:
    """fan_in/fan_out matching the reference's GlorotUniform task
    (initializer.cc): dense (in,out); conv (out,in,kh,kw) uses
    receptive-field scaling."""
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    if len(shape) == 4:  # conv OIHW
        rf = shape[2] * shape[3]
        return shape[1] * rf, shape[0] * rf
    # attention (in, heads, d) etc.: fold trailing dims
    fan_in = shape[0]
    fan_out = 1
    for s in shape[1:]:
        fan_out *= s
    return fan_in, fan_out


def glorot_uniform(key, shape, dtype=jnp.float32, fan_in=None, fan_out=None):
    if fan_in is None or fan_out is None:
        fan_in, fan_out = _fans(shape)
    scale = math.sqrt(6.0 / (fan_in + fan_out))
    return jax.random.uniform(key, shape, dtype, -scale, scale)


def zeros(key, shape, dtype=jnp.float32):
    return jnp.zeros(shape, dtype)


def ones(key, shape, dtype=jnp.float32):
    return jnp.ones(shape, dtype)


def make_constant(value: float):
    def init(key, shape, dtype=jnp.float32):
        return jnp.full(shape, value, dtype)
    return init


def make_uniform(minv: float, maxv: float, seed: int = 0):
    def init(key, shape, dtype=jnp.float32, **_fans):
        # a range of its own: a spec's fan_in / fan_out do not move it
        return jax.random.uniform(key, shape, dtype, minv, maxv)
    return init


def make_signed_uniform(lo: float, hi: float):
    """Magnitudes uniform in [lo, hi], each with a random sign: values
    AWAY from zero on both sides of it."""
    def init(key, shape, dtype=jnp.float32, **_fans):
        k1, k2 = jax.random.split(key)
        sign = jnp.where(jax.random.bernoulli(k1, 0.5, shape), 1.0, -1.0)
        return (sign * jax.random.uniform(k2, shape, jnp.float32, lo, hi)
                ).astype(dtype)
    return init


def range_init(spec):
    """(lo, hi) -> uniform in it; (lo, hi, "signed") -> magnitudes in
    it with a random sign: how a configuration states where a scale
    starts."""
    lo, hi, *kind = spec
    if kind and kind[0] != "signed":
        raise ValueError(f"a range is (lo, hi) or (lo, hi, 'signed'), "
                         f"not {spec!r}")
    return (make_signed_uniform if kind else make_uniform)(
        float(lo), float(hi))


def make_normal(mean: float = 0.0, stddev: float = 1.0, seed: int = 0):
    def init(key, shape, dtype=jnp.float32, **_fans):
        # a deviation of its own: a spec's fan_in / fan_out do not move it
        return mean + stddev * jax.random.normal(key, shape, dtype)
    return init


def named(initializer, name: str):
    """An op's `kernel_initializer`: one for all its matrices, or a dict
    with one a matrix by name."""
    return initializer[name] if isinstance(initializer, dict) \
        else initializer


def make_normal_as(stddev: float, dtype, blocks: int = 16):
    """normal(0, stddev) for a matrix too large to draw at once beside
    the rest of a model (Falcon-H1's 261,120 x 5,120 head: 5 GB in f32,
    twice that while `make_normal` scales it): drawn in f32 a block of
    rows at a time into a buffer of `dtype` — what an f32-declared
    weight is STORED as (FFConfig.param_dtype), so the executor's cast
    after it is no copy; a weight declared in another dtype keeps
    that."""
    def init(key, shape, asked=jnp.float32, **_fans):
        out = jnp.dtype(dtype) if jnp.dtype(asked) == jnp.float32 \
            else jnp.dtype(asked)
        n = max(b for b in range(1, blocks + 1) if shape[0] % b == 0)
        rows = shape[0] // n

        def a_block(i, buf):
            blk = stddev * jax.random.normal(
                jax.random.fold_in(key, i), (rows,) + tuple(shape[1:]),
                jnp.float32)
            return jax.lax.dynamic_update_slice_in_dim(
                buf, blk.astype(out), i * rows, 0)

        return jax.jit(lambda: jax.lax.fori_loop(
            0, n, a_block, jnp.zeros(shape, out)))()
    return init


def he_normal(key, shape, dtype=jnp.float32, fan_in=None, fan_out=None):
    if fan_in is None:
        fan_in, _ = _fans(shape)
    return jax.random.normal(key, shape, dtype) * math.sqrt(2.0 / fan_in)


INITIALIZERS: Dict[str, Callable] = {
    "glorot": glorot_uniform,
    "glorot_uniform": glorot_uniform,
    "zeros": zeros,
    "zero": zeros,
    "ones": ones,
    "he_normal": he_normal,
    "norm": make_normal(),
    "normal": make_normal(),
}


def resolve(name_or_fn) -> Callable:
    if callable(name_or_fn):
        return name_or_fn
    return INITIALIZERS[name_or_fn]
