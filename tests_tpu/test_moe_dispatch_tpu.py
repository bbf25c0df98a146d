"""On-chip MoE dispatch A/B: dense GShard masks vs sorted-scatter
routing (round 4, VERDICT r3 #8). Correctness parity is pinned by the
CPU suite (tests/test_expert_parallel.py); this leg records REAL chip
timings so the auto threshold (ops/moe.py DENSE_MASK_ELEMENT_LIMIT)
stops being folklore. It prints them and asserts no winner."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import AdamOptimizer, FFConfig, FFModel


def build(mode, n_tokens, e, hidden):
    cfg = FFConfig()
    cfg.batch_size = n_tokens
    cfg.moe_dispatch = mode
    ff = FFModel(cfg)
    x = ff.create_tensor((n_tokens, 64), name="input")
    t = ff.moe_ffn(x, num_experts=e, k=2, hidden_dim=hidden, name="moe")
    t = ff.dense(t, 10, name="head")
    ff.softmax(t)
    ff.compile(optimizer=AdamOptimizer(lr=1e-3),
               loss_type="sparse_categorical_crossentropy", metrics=[])
    return ff


def step_ms(ff, batch, steps=20):
    m = ff.train_batch(batch)
    float(m["loss"])  # device->host fetch closes the timed region
    t0 = time.perf_counter()
    for _ in range(steps):
        m = ff.train_batch(batch)
    float(m["loss"])
    return (time.perf_counter() - t0) / steps * 1e3


@pytest.mark.parametrize("e,n_tokens,hidden", [
    (8, 512, 512),      # 1.3M mask elements: BELOW the auto threshold
    (8, 4096, 512),     # 84M: just past it at small E
    (64, 8192, 512),    # 335M: large E, the sorted path's reason to be
])
def test_dispatch_ab_on_chip(e, n_tokens, hidden):
    rng = np.random.RandomState(0)
    batch = {"input": jnp.asarray(rng.randn(n_tokens, 64), jnp.float32),
             "label": jnp.asarray(rng.randint(0, 10, n_tokens),
                                  jnp.int32)}
    results = {}
    moe_op = None
    for mode in ("dense", "sorted"):
        ff = build(mode, n_tokens, e, hidden)
        moe_op = next(o for o in ff.ops if o.op_type == "moe_ffn")
        results[mode] = step_ms(ff, batch)
        l0 = float(ff.train_batch(batch)["loss"])
        assert np.isfinite(l0)
    # report what auto actually selects, via the REAL policy + the
    # op's real capacity (these timings exist to recalibrate
    # DENSE_MASK_ELEMENT_LIMIT — don't re-derive it by hand)
    from flexflow_tpu.ops.moe import use_sorted_dispatch

    class _AutoHolder:  # the loop's last model has moe_dispatch FORCED;
        config = FFConfig()  # the label must reflect the auto policy

    auto = use_sorted_dispatch(_AutoHolder(), n_tokens * moe_op.k, e,
                               moe_op.capacity, expert_sharded=False)
    print(f"\n[moe-dispatch A/B] E={e} tokens={n_tokens} "
          f"cap={moe_op.capacity}: "
          f"dense {results['dense']:.2f} ms  "
          f"sorted {results['sorted']:.2f} ms  "
          f"(auto picks {'sorted' if auto else 'dense'})")
    # both paths must run on chip; the printed timings calibrate the
    # threshold — no winner asserted (shape-dependent by design)
    assert results["dense"] > 0 and results["sorted"] > 0
