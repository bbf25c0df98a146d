"""Pallas multi-timestep LSTM kernel COMPILED on-chip: forward and
gradients vs the scan path at NMT shapes (ops/rnn.py use_pallas picks
it; the scan stays the default until the chip benchmark says
otherwise). Reference: nmt/lstm.cu, the cuDNN recurrence this replaces.
Analysis: under scan XLA re-reads wh (8 MB bf16 at H=1024) from HBM
every timestep — T=40 steps stream 320 MB for ~21 GFLOP; the kernel
keeps wh VMEM-resident."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels.lstm_scan import lstm_sequence, scan_reference


def make(T, B, H, dtype, seed=0):
    rng = np.random.RandomState(seed)
    xg = jnp.asarray(rng.randn(T, B, 4 * H) * 0.3, dtype)
    wh = jnp.asarray(rng.randn(H, 4 * H) * 0.05, dtype)
    h0 = jnp.zeros((B, H), dtype)
    c0 = jnp.zeros((B, H), dtype)
    return xg, wh, h0, c0


@pytest.mark.parametrize("dtype,atol", [(jnp.bfloat16, 5e-2),
                                        (jnp.float32, 1e-4)])
def test_lstm_kernel_compiled_matches_scan(dtype, atol):
    xg, wh, h0, c0 = make(T=40, B=64, H=1024, dtype=dtype)
    ys = jax.jit(lambda a, b, c, d: lstm_sequence(a, b, c, d))(
        xg, wh, h0, c0)
    want = scan_reference(xg, wh, h0, c0)
    err = np.max(np.abs(np.asarray(ys, np.float32)
                        - np.asarray(want, np.float32)))
    assert err < atol, err


@pytest.mark.parametrize("dtype,atol", [(jnp.bfloat16, 2e-1),
                                        (jnp.float32, 1e-3)])
def test_lstm_kernel_compiled_grads_match_scan(dtype, atol):
    """The time-reversed backward kernel (gates recomputed from the
    stashed h/c) against autodiff through the scan. Tolerances are
    absolute on sums over T=40 steps and B=64 rows: ~50x the forward's
    per-element bound."""
    xg, wh, h0, c0 = make(T=40, B=64, H=1024, dtype=dtype)

    def grads(fn):
        return jax.jit(jax.grad(
            lambda a, b: jnp.sum(fn(a, b, h0, c0).astype(jnp.float32)),
            argnums=(0, 1)))(xg, wh)

    for got, want, name in zip(grads(lstm_sequence),
                               grads(scan_reference), ("dxg", "dwh")):
        err = np.max(np.abs(np.asarray(got, np.float32)
                            - np.asarray(want, np.float32)))
        scale = np.max(np.abs(np.asarray(want, np.float32)))
        assert err < atol * max(1.0, scale), (name, err, scale)
