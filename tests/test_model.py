"""End-to-end training tests: graph building, compile, fit.

Pattern follows reference tests/accuracy_tests.sh — train few epochs on a
small problem and assert the model actually learns."""

import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, SGDOptimizer


def make_mlp(config=None):
    ff = FFModel(config or FFConfig())
    x = ff.create_tensor((config.batch_size if config else 64, 16),
                         name="input")
    t = ff.dense(x, 32, activation="relu")
    t = ff.dense(t, 4)
    t = ff.softmax(t)
    return ff


def synthetic_classification(n=512, d=16, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, d).astype(np.float32)
    w = rng.randn(d, classes).astype(np.float32)
    y = np.argmax(x @ w + 0.1 * rng.randn(n, classes), axis=1).astype(np.int32)
    return x, y


def test_mlp_learns():
    cfg = FFConfig()
    cfg.batch_size = 64
    ff = make_mlp(cfg)
    ff.compile(optimizer=SGDOptimizer(lr=0.1),
               loss_type="sparse_categorical_crossentropy",
               metrics=["accuracy"])
    x, y = synthetic_classification()
    hist = ff.fit({"input": x}, y, epochs=12, verbose=False)
    assert hist[-1]["accuracy"] > 0.8, hist[-1]
    assert hist[-1]["loss"] < hist[0]["loss"]


def test_mlp_adam_learns():
    cfg = FFConfig()
    cfg.batch_size = 64
    ff = make_mlp(cfg)
    ff.compile(optimizer=AdamOptimizer(lr=0.01),
               loss_type="sparse_categorical_crossentropy",
               metrics=["accuracy"])
    x, y = synthetic_classification()
    hist = ff.fit({"input": x}, y, epochs=8, verbose=False)
    assert hist[-1]["accuracy"] > 0.8, hist[-1]


def test_cnn_trains_and_bn_state_updates():
    cfg = FFConfig()
    cfg.batch_size = 16
    ff = FFModel(cfg)
    x = ff.create_tensor((16, 3, 8, 8), name="input")
    t = ff.conv2d(x, 8, 3, 3, 1, 1, 1, 1, activation="relu")
    t = ff.batch_norm(t, relu=True)
    t = ff.pool2d(t, 2, 2, 2, 2, 0, 0)
    t = ff.flat(t)
    t = ff.dense(t, 4)
    t = ff.softmax(t)
    ff.compile(optimizer=SGDOptimizer(lr=0.05),
               loss_type="sparse_categorical_crossentropy",
               metrics=["accuracy"])
    rng = np.random.RandomState(0)
    xs = rng.randn(64, 3, 8, 8).astype(np.float32)
    ys = (xs.mean(axis=(1, 2, 3)) > 0).astype(np.int32)
    rm_before = np.asarray(
        ff.state.states["batch_norm"]["running_mean"]).copy()
    hist = ff.fit({"input": xs}, ys, epochs=3, verbose=False)
    rm_after = np.asarray(ff.state.states["batch_norm"]["running_mean"])
    assert not np.allclose(rm_before, rm_after), "BN stats must update"
    assert np.isfinite(hist[-1]["loss"])


def test_weight_get_set_roundtrip():
    cfg = FFConfig()
    ff = make_mlp(cfg)
    ff.compile()
    w = ff.get_weights("dense")
    assert w["kernel"].shape == (16, 32)
    neww = {k: np.zeros_like(v) for k, v in w.items()}
    ff.set_weights("dense", neww)
    w2 = ff.get_weights("dense")
    np.testing.assert_allclose(w2["kernel"], 0.0)


def test_mse_regression_learns():
    cfg = FFConfig()
    cfg.batch_size = 32
    ff = FFModel(cfg)
    x = ff.create_tensor((32, 8), name="input")
    t = ff.dense(x, 16, activation="tanh")
    t = ff.dense(t, 1)
    ff.compile(optimizer=AdamOptimizer(lr=0.01),
               loss_type="mean_squared_error", metrics=[])
    rng = np.random.RandomState(0)
    xs = rng.randn(256, 8).astype(np.float32)
    ys = (xs.sum(axis=1, keepdims=True) * 0.1).astype(np.float32)
    hist = ff.fit({"input": xs}, ys, epochs=10, verbose=False)
    assert hist[-1]["loss"] < hist[0]["loss"] * 0.5


def test_summary():
    cfg = FFConfig()
    ff = make_mlp(cfg)
    s = ff.summary()
    assert "dense" in s and "total params" in s


def test_hlo_cost_extraction(rng):
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    from flexflow_tpu.utils.profiling import hlo_cost
    cfg = FFConfig(); cfg.batch_size = 8
    ff = FFModel(cfg)
    x = ff.create_tensor((8, 16), name="input")
    h = ff.dense(x, 32, activation="relu", name="fc1")
    ff.softmax(ff.dense(h, 10, name="fc2"), name="sm")
    ff.compile(optimizer=SGDOptimizer(lr=0.1),
               loss_type="sparse_categorical_crossentropy", metrics=[])
    c = hlo_cost(ff, {"input": rng.randn(8, 16).astype(np.float32),
                      "label": rng.randint(0, 10, 8).astype(np.int32)})
    assert c.get("flops", 0) > 0


def test_imported_weights_applied_at_compile(rng):
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    cfg = FFConfig(); cfg.batch_size = 4
    ff = FFModel(cfg)
    x = ff.create_tensor((4, 8), name="input")
    ff.softmax(ff.dense(x, 3, name="fc"), name="sm")
    w = rng.randn(8, 3).astype(np.float32)
    ff.imported_weights["fc"] = {"kernel": w}
    ff.compile(optimizer=SGDOptimizer(lr=0.1),
               loss_type="sparse_categorical_crossentropy", metrics=[])
    np.testing.assert_allclose(ff.get_weights("fc")["kernel"], w)


def test_train_batches_matches_sequential():
    """The scanned multi-step dispatch (train_batches, the trace-replay
    analog of alexnet.cc:106-111 begin/end_trace) must reproduce the
    single-step stream EXACTLY: same rng fold_in sequence, same updates."""
    import jax

    rng = np.random.RandomState(3)
    batches = [{"input": rng.randn(8, 16).astype(np.float32),
                "label": rng.randint(0, 4, (8,))} for _ in range(4)]

    def build():
        cfg = FFConfig()
        cfg.batch_size = 8
        ff = FFModel(cfg)
        t = ff.create_tensor((8, 16), name="input")
        h = ff.dense(t, 32, activation="relu")
        h = ff.dropout(h, 0.1)
        ff.dense(h, 4)
        ff.compile(optimizer=SGDOptimizer(lr=0.1),
                   loss_type="sparse_categorical_crossentropy",
                   metrics=["accuracy"])
        return ff

    seq = build()
    seq_losses = [float(seq.train_batch(b)["loss"]) for b in batches]

    grouped = build()
    ms = grouped.train_batches(batches[:3])   # one dispatch, 3 steps
    tail = grouped.train_batch(batches[3])    # ragged tail, single step
    assert jax.device_get(ms["loss"]).shape == (3,)
    got = list(jax.device_get(ms["loss"])) + [float(tail["loss"])]
    np.testing.assert_allclose(seq_losses, got, rtol=1e-6)
    name = seq.ops[-1].name
    for k, v in seq.get_weights(name).items():
        np.testing.assert_allclose(v, grouped.get_weights(name)[k],
                                   rtol=1e-5)


def test_train_batches_unrolled_matches_scan():
    """config.multi_step_unroll=True (the big-param body that avoids the
    TPU scan carry's double-buffering — DLRM 26x1M tables OOM'd the
    scanned program on a v5e) must be bit-compatible with the scanned
    body."""
    import jax

    rng = np.random.RandomState(7)
    batches = [{"input": rng.randn(8, 16).astype(np.float32),
                "label": rng.randint(0, 4, (8,))} for _ in range(3)]

    def build(unroll):
        cfg = FFConfig()
        cfg.batch_size = 8
        cfg.multi_step_unroll = unroll
        ff = FFModel(cfg)
        t = ff.create_tensor((8, 16), name="input")
        h = ff.dense(t, 32, activation="relu")
        ff.dense(h, 4)
        ff.compile(optimizer=SGDOptimizer(lr=0.1),
                   loss_type="sparse_categorical_crossentropy",
                   metrics=["accuracy"])
        return ff

    scan, unrolled = build(False), build(True)
    ls = jax.device_get(scan.train_batches(batches)["loss"])
    lu = jax.device_get(unrolled.train_batches(batches)["loss"])
    assert ls.shape == lu.shape == (3,)
    np.testing.assert_allclose(ls, lu, rtol=1e-6)
    name = scan.ops[-1].name
    for k, v in scan.get_weights(name).items():
        np.testing.assert_allclose(v, unrolled.get_weights(name)[k],
                                   rtol=1e-5)


def test_fit_steps_per_dispatch():
    ff = make_mlp()
    ff.compile(optimizer=SGDOptimizer(lr=0.1),
               loss_type="sparse_categorical_crossentropy",
               metrics=["accuracy"])
    x, y = synthetic_classification()
    h1 = ff.fit({"input": x}, y, epochs=2, steps_per_dispatch=4,
                verbose=False)
    assert len(h1) == 2
    assert h1[-1]["loss"] < h1[0]["loss"]


def test_fit_prefetch_matches_direct():
    """fit(prefetch=True) rides the (native, if available) double-
    buffered loader but must reproduce the direct path's losses exactly
    — same permutation stream, same batches, same updates."""
    x, y = synthetic_classification()

    def run(prefetch):
        ff = make_mlp()
        ff.compile(optimizer=SGDOptimizer(lr=0.1),
                   loss_type="sparse_categorical_crossentropy",
                   metrics=["accuracy"])
        return ff.fit({"input": x}, y, epochs=3, verbose=False,
                      steps_per_dispatch=2, prefetch=prefetch)

    ha, hb = run(False), run(True)
    for ma, mb in zip(ha, hb):
        np.testing.assert_allclose(ma["loss"], mb["loss"], rtol=1e-6)
        np.testing.assert_allclose(ma.get("accuracy", 0),
                                   mb.get("accuracy", 0), rtol=1e-6)


def test_evaluate_steps_per_dispatch_matches():
    ff = make_mlp()
    ff.compile(optimizer=SGDOptimizer(lr=0.1),
               loss_type="sparse_categorical_crossentropy",
               metrics=["accuracy"])
    x, y = synthetic_classification(n=320)
    ff.fit({"input": x}, y, epochs=2, verbose=False)
    a = ff.evaluate({"input": x}, y)
    b = ff.evaluate({"input": x}, y, steps_per_dispatch=3)  # ragged tail
    np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-6)
    np.testing.assert_allclose(a["accuracy"], b["accuracy"], rtol=1e-6)


def test_comp_mode_inference():
    """compile(comp_mode=INFERENCE): no optimizer slots are allocated
    (reference COMP_MODE_INFERENCE, ffconst.h), forward/evaluate work,
    and training fails with a clear error instead of a silent step."""
    from flexflow_tpu.config import CompMode

    cfg = FFConfig()
    cfg.batch_size = 8
    ff = FFModel(cfg)
    x = ff.create_tensor((8, 16), name="input")
    t = ff.dense(x, 32, activation="relu")
    ff.softmax(ff.dense(t, 4))
    ff.compile(optimizer=AdamOptimizer(lr=0.01),
               loss_type="sparse_categorical_crossentropy",
               metrics=["accuracy"], comp_mode=CompMode.INFERENCE)
    assert ff.state.opt_state == {}  # no m/v slots
    rng = np.random.RandomState(0)
    b = {"input": rng.randn(8, 16).astype(np.float32),
         "label": rng.randint(0, 4, 8).astype(np.int32)}
    logits = ff.forward(b)
    assert logits.shape == (8, 4)
    m = ff.evaluate({"input": b["input"]}, b["label"])
    assert "loss" in m
    with pytest.raises(RuntimeError, match="INFERENCE"):
        ff.train_batch(b)
    # training compile of the same graph allocates the slots
    ff.compile(optimizer=AdamOptimizer(lr=0.01),
               loss_type="sparse_categorical_crossentropy", metrics=[])
    assert ff.state.opt_state
    assert np.isfinite(float(ff.train_batch(b)["loss"]))
    # typos must fail loudly, not silently compile for training
    with pytest.raises(ValueError, match="comp_mode"):
        ff.compile(optimizer=AdamOptimizer(lr=0.01),
                   loss_type="sparse_categorical_crossentropy",
                   metrics=[], comp_mode="Inference")


def test_inference_restores_training_checkpoint(tmp_path):
    """train -> checkpoint -> inference-compile -> restore: the on-disk
    optimizer slots are skipped (not structure-mismatched) and the
    restored forward matches the training model's."""
    from flexflow_tpu.config import CompMode
    from flexflow_tpu.core.checkpoint import restore_model, save_model

    def build(mode):
        cfg = FFConfig()
        cfg.batch_size = 8
        ff = FFModel(cfg)
        x = ff.create_tensor((8, 16), name="input")
        ff.softmax(ff.dense(ff.dense(x, 32, activation="relu"), 4))
        ff.compile(optimizer=AdamOptimizer(lr=0.01),
                   loss_type="sparse_categorical_crossentropy",
                   metrics=[], comp_mode=mode)
        return ff

    rng = np.random.RandomState(0)
    b = {"input": rng.randn(8, 16).astype(np.float32),
         "label": rng.randint(0, 4, 8).astype(np.int32)}
    ff = build(CompMode.TRAINING)
    ff.train_batch(b)
    save_model(ff, str(tmp_path / "ckpt"))
    fi = build(CompMode.INFERENCE)
    restore_model(fi, str(tmp_path / "ckpt"))
    assert int(fi.state.step) == 1 and fi.state.opt_state == {}
    np.testing.assert_allclose(np.asarray(fi.forward(b)),
                               np.asarray(ff.forward(b)), rtol=1e-6)
