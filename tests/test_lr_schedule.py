"""Runtime learning-rate control (reference keras LearningRateScheduler,
python/flexflow/keras/callbacks.py:49-62): the lr rides the jitted step
as a traced scalar, so schedules re-dispatch without recompiling."""

import numpy as np
import pytest

from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, SGDOptimizer


def build(lr=0.1, opt="sgd"):
    cfg = FFConfig(batch_size=32)
    ff = FFModel(cfg)
    x = ff.create_tensor((32, 16), name="input")
    t = ff.dense(x, 32, activation="relu", name="fc0")
    ff.softmax(ff.dense(t, 4, name="head"))
    optimizer = (SGDOptimizer(lr=lr, momentum=0.9) if opt == "sgd"
                 else AdamOptimizer(lr=lr))
    ff.compile(optimizer=optimizer,
               loss_type="sparse_categorical_crossentropy", metrics=[])
    return ff


def batch(seed=0):
    rng = np.random.RandomState(seed)
    return {"input": rng.randn(32, 16).astype(np.float32),
            "label": rng.randint(0, 4, 32).astype(np.int32)}


def test_zero_lr_freezes_weights():
    ff = build()
    ff.set_learning_rate(0.0)
    w0 = ff.get_weights("fc0")["kernel"]
    ff.train_batch(batch())
    np.testing.assert_array_equal(w0, ff.get_weights("fc0")["kernel"])


@pytest.mark.parametrize("opt", ["sgd", "adam"])
def test_scaled_lr_matches_native_lr(opt):
    """set_learning_rate(2*base) must produce exactly the step an
    optimizer built with lr=2*base produces."""
    ff_a = build(lr=0.05, opt=opt)
    ff_b = build(lr=0.10, opt=opt)
    ff_b.set_weights("fc0", ff_a.get_weights("fc0"))
    ff_b.set_weights("head", ff_a.get_weights("head"))
    ff_a2 = build(lr=0.05, opt=opt)
    ff_a2.set_weights("fc0", ff_a.get_weights("fc0"))
    ff_a2.set_weights("head", ff_a.get_weights("head"))
    ff_a2.set_learning_rate(0.10)
    b = batch()
    ff_b.train_batch(b)
    ff_a2.train_batch(b)
    for n in ("fc0", "head"):
        np.testing.assert_allclose(ff_a2.get_weights(n)["kernel"],
                                   ff_b.get_weights(n)["kernel"],
                                   rtol=1e-6, atol=1e-7)


def test_schedule_changes_without_recompile():
    """Changing the lr between steps must not trigger a retrace: the
    program registry must count ONE train_step compile after steps at
    different lrs (the count would grow if lr ever became a
    static/value-keyed argument — the lr rides as a traced device
    scalar, so its signature is shape/dtype, never the value)."""
    ff = build()
    ff.train_batch(batch())
    assert ff.executor.compile_counts().get("train_step") == 1
    ff.set_learning_rate(0.01)
    ff.train_batch(batch(1))
    ff.set_learning_rate(0.002)
    ff.train_batch(batch(2))
    assert ff.executor.compile_counts().get("train_step") == 1
    assert ff.get_learning_rate() == pytest.approx(0.002)


def test_lr_scale_applies_under_grad_accum():
    """The accum path must honor the schedule too: zero lr through
    train_batch_accum leaves weights untouched."""
    ff = build()
    ff.set_learning_rate(0.0)
    w0 = ff.get_weights("fc0")["kernel"]
    b = batch()
    micro = [{k: v[i * 8:(i + 1) * 8] for k, v in b.items()}
             for i in range(4)]
    ff.train_batch_accum(micro)
    np.testing.assert_array_equal(w0, ff.get_weights("fc0")["kernel"])


def test_keras_lr_scheduler_callback():
    from flexflow_tpu.frontends.keras import (
        LearningRateScheduler, Model)
    from flexflow_tpu.frontends.keras.layers import Dense, Input
    x = Input(shape=(16,))
    t = Dense(32, activation="relu")(x)
    out = Dense(4, activation="softmax")(t)
    m = Model(inputs=[x], outputs=out)
    m.compile(optimizer=SGDOptimizer(lr=0.1),
              loss="sparse_categorical_crossentropy",
              metrics=["accuracy"])
    rng = np.random.RandomState(0)
    xs = rng.randn(64, 16).astype(np.float32)
    ys = rng.randint(0, 4, 64).astype(np.int32)
    seen = []
    sched = LearningRateScheduler(lambda e: [0.1, 0.0][e])
    m.fit(xs, ys, batch_size=32, epochs=1, callbacks=[sched],
          shuffle=False, verbose=False)
    w_after_e0 = m.ffmodel.get_weights("dense_1")["kernel"].copy()
    m.fit(xs, ys, batch_size=32, epochs=1,
          callbacks=[LearningRateScheduler(lambda e: 0.0)],
          shuffle=False, verbose=False)
    np.testing.assert_array_equal(
        w_after_e0, m.ffmodel.get_weights("dense_1")["kernel"])


def test_lr_device_scalar_is_cached():
    """The lr scalar handed to every dispatch must be the SAME device
    buffer until set_learning_rate changes it: re-making it per dispatch
    put one synchronous host->device transfer on each train_batches
    call, serializing the async dispatch queue on host round trips —
    the round-4 on-chip regression."""
    ff = build(lr=0.1)
    ex = ff.executor
    a, b = ex._lr(), ex._lr()
    assert a is b
    ff.set_learning_rate(0.05)
    c = ex._lr()
    assert c is not a
    assert float(c) == pytest.approx(0.5)  # scale vs base lr 0.1
    assert ex._lr() is c
