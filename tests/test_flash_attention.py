"""Flash-attention Pallas kernels vs XLA reference (interpret mode on CPU).

Reference analog: tests/ops golden tests (SURVEY.md section 4.3) — same
computation in plain numpy/XLA, assert_allclose on outputs AND gradients.
"""

import functools
import re
import types

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu.kernels import flash_attention as fa
from flexflow_tpu.kernels.flash_attention import flash_attention_bshd


def xla_attention(q, k, v, causal):
    """The XLA path of ops/attention.py::_attend."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / np.sqrt(d)
    if causal:
        lq, lk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((lq, lk), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def qkv(rng, b, sq, sk, h, d, dtype=jnp.float32):
    return (jnp.asarray(rng.randn(b, sq, h, d), dtype),
            jnp.asarray(rng.randn(b, sk, h, d), dtype),
            jnp.asarray(rng.randn(b, sk, h, d), dtype))


def out_and_grads(fn, q, k, v):
    def loss(q, k, v):
        return jnp.sum(jnp.sin(fn(q, k, v).astype(jnp.float32)))
    return (fn(q, k, v),) + jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


# (heads, head_dim): two heads a 128-lane slab; an ODD count of 64-wide
# heads (one zero head pads the last slab); one 128-wide head a slab
# (the kernels' arithmetic before the packing); four 32-wide heads a slab
PACKINGS = [(4, 64), (3, 64), (2, 128), (8, 32)]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk", [(128, 128), (128, 256), (256, 128)])
@pytest.mark.parametrize("h,d", PACKINGS)
def test_packed_kernels_match_xla(rng, h, d, sq, sk, causal, dtype):
    """Output and all three gradients of the packed kernels against the
    XLA path, read and written as (b, s, h*d)."""
    q, k, v = qkv(rng, 1, sq, sk, h, d, dtype)
    got = out_and_grads(functools.partial(
        flash_attention_bshd, causal=causal, interpret=True), q, k, v)
    want = out_and_grads(functools.partial(xla_attention, causal=causal),
                         q, k, v)
    tol = 2e-3 if dtype == jnp.float32 else 6e-2
    for a, b, name in zip(got, want, ("o", "dq", "dk", "dv")):
        assert a.dtype == dtype and a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=tol, atol=tol, err_msg=f"{name} mismatch")


@pytest.mark.parametrize("block_q,block_k", [(128, 256), (256, 128),
                                             (256, 256)])
def test_blocks_larger_than_the_floor(rng, block_q, block_k):
    """Unequal and larger-than-128 blocks: the diagonal's k-blocks (or
    q-blocks, for dK/dV) alone carry the mask, whatever their ratio."""
    q, k, v = qkv(rng, 1, 512, 512, 2, 64)
    got = out_and_grads(functools.partial(
        flash_attention_bshd, causal=True, interpret=True, block_q=block_q,
        block_k=block_k), q, k, v)
    want = out_and_grads(functools.partial(xla_attention, causal=True),
                         q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("h,d", PACKINGS)
def test_flash_reads_the_projections_layout(rng, h, d):
    """Forward and backward take q, k, v as the (b, s, h*d) arrays the
    projections leave: the lowered text transposes nothing of their
    size (the kernels' own tile transposes and the (b, s, h) delta are
    smaller)."""
    b, s = 1, 256
    q, k, v = qkv(rng, b, s, s, h, d)

    def loss(q, k, v):
        return jnp.sum(flash_attention_bshd(q, k, v, causal=True,
                                            interpret=True))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, v).as_text()
    seen = 0
    for line in text.splitlines():
        if "stablehlo.transpose" not in line:
            continue
        seen += 1
        dims = re.search(r"\(tensor<([\dx]+)x\w+>\)", line).group(1)
        assert np.prod([int(n) for n in dims.split("x")]) < b * s * h * d, \
            line
    assert seen         # the pattern above does read this text


@pytest.mark.parametrize("b,h,sq,sk,d,want", [
    (1, 32, 2048, 2048, 64, True),    # pretrain-1chip's attention
    (1, 16, 2048, 2048, 64, True),    # its shard on a 2 x 2 mesh
    (1, 32, 128, 128, 64, False),
    (1, 32, 512, 512, 64, False),     # 32 MiB of scores: VMEM holds them
    (1, 32, 1024, 1024, 64, True),
    (2, 16, 1024, 1024, 128, True),   # d = 128 as before
    (2, 16, 512, 512, 128, False),
    (1, 64, 512, 512, 32, True),      # 64 MiB: over the line
    (1, 3, 2048, 2048, 64, False),    # 48 MiB, and one zero head of four
    (1, 3, 4096, 4096, 64, True),     # the odd head count, padded
    (1, 16, 1024, 1024, 320, False),  # head_dim > 256: not the kernel's
    (1, 16, 1000, 1000, 64, False),   # not whole 128-blocks
])
def test_flash_gate_table(b, h, sq, sk, d, want):
    """The auto rule over the shapes of the call (the backend aside)."""
    got = (fa.flash_unsupported(sq, sk, d) is None
           and fa.flash_profitable(b, h, sq, sk, d))
    assert got == want
    # and the tri-state: forced either way, whatever the shape says
    assert fa.resolve_flash(True, b, h, sq, sk, d) is True
    assert fa.resolve_flash(False, b, h, sq, sk, d) is False
    assert fa.resolve_flash(None, b, h, sq, sk, d) is False   # a CPU here


def test_block_rule_and_table():
    assert fa.choose_flash_blocks(128, 384) == (128, 128, 128, 128)
    bq, bk, bq2, bk2 = fa.choose_flash_blocks(2048, 2048, 2)
    assert 2048 % bq == 0 and 2048 % bk == 0 and bq >= 256 and bk >= 256
    assert 2048 % bq2 == 0 and 2048 % bk2 == 0
    fa.register_flash_blocks(640, 640, 1, 4, (128, 128, 128, 128))
    try:
        assert fa.choose_flash_blocks(640, 640, 1, 4) == (128,) * 4
    finally:
        fa._BLOCK_TABLE.pop((640, 640, 1, 4))


def _interpreted(monkeypatch):
    """The op's own dispatch, its kernel call run by the interpreter (a
    test's steering: the program has no such option)."""
    monkeypatch.setattr(fa, "flash_attention_bshd", functools.partial(
        flash_attention_bshd, interpret=True))


def _attention_model(layers, batch=2, seq=128, embed=128, heads=2,
                     use_flash=True, telemetry=False):
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
    cfg = FFConfig()
    cfg.batch_size = batch
    ff = FFModel(cfg)
    t = ff.create_tensor((batch, seq, embed), name="input")
    for i in range(layers):
        a = ff.multihead_attention(t, t, t, embed, heads, causal=True,
                                   use_flash=use_flash, name=f"layer{i}_attn")
        t = ff.add(a, t)
    ff.softmax(ff.dense(ff.reshape(t, (batch, seq * embed)), 4))
    ff.compile(optimizer=SGDOptimizer(lr=0.05),
               loss_type="sparse_categorical_crossentropy", metrics=[])
    rng = np.random.RandomState(0)
    batch = {"input": rng.randn(batch, seq, embed).astype(np.float32),
             "label": rng.randint(0, 4, batch).astype(np.int32)}
    return ff, batch


def test_one_lowering_per_kernel_for_an_8_layer_model(monkeypatch):
    """Eight layers make the same three calls: each kernel is traced
    and lowered ONCE (the nested jit), and called eight times."""
    _interpreted(monkeypatch)
    ff, batch = _attention_model(8)
    ex = ff.executor
    text = ex.build_train_step().lower(
        ff.state, ex.shard_batch(batch), jax.random.PRNGKey(0),
        ex._lr()).as_text()
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        defs = re.findall(rf"func\.func private @{kernel}(?:_\d+)?\(", text)
        calls = re.findall(rf"call @{kernel}(?:_\d+)?\(", text)
        assert (len(defs), len(calls)) == (1, 8), (kernel, defs, calls)
    assert ff.attn_impl_counts() == {"flash": 8, "xla": 0}
    assert "shard_map" not in str(jax.make_jaxpr(ex._step_body)(
        ff.state, ex.shard_batch(batch), jax.random.PRNGKey(0), ex._lr()))


def test_attn_impl_tally_on_the_bus(monkeypatch):
    """The step's compile says once which core each attention op took:
    an instant on the telemetry bus, and a line of summary()."""
    from flexflow_tpu.utils.telemetry import Telemetry
    _interpreted(monkeypatch)
    ff, batch = _attention_model(2)
    xla = ff.ops[0]
    xla.use_flash = False
    ff.telemetry = Telemetry(enabled=True)
    assert "attention cores" not in ff.summary()
    for _ in range(2):
        ff.train_batch(batch)
    said = [e for e in ff.telemetry.events if e[2] == "attn_impl"]
    assert len(said) == 1
    assert said[0][0] == "i" and said[0][1] == ("train", "compile")
    assert said[0][6] == {"flash": 1, "xla": 1}
    assert "attention cores: flash 1, xla 1" in ff.summary()


def test_sharded_call_is_per_shard_and_equals_one_device(monkeypatch, rng):
    """Under a 2 x 2 mesh the op calls the kernels inside shard_map
    over its sample and head axes and the gate reads the PER-SHARD
    shapes; on one device the call is direct. Same numbers."""
    from flexflow_tpu import FFConfig, FFModel, make_mesh
    from flexflow_tpu.op import OpContext

    _interpreted(monkeypatch)
    b, s, h, d = 4, 128, 4, 64
    ff = FFModel(FFConfig())
    x = ff.create_tensor((b, s, h * d), name="x")
    ff.multihead_attention(x, x, x, h * d, h, causal=True, name="mha")
    op = ff.ops[0]
    params = {n: jnp.asarray(rng.randn(*w.shape) * 0.05, jnp.float32)
              for n, w in op.weight_specs().items()}
    xin = jnp.asarray(rng.randn(b, s, h * d), jnp.float32)
    seen = []
    monkeypatch.setattr(fa, "resolve_flash",
                        lambda use, *shape: seen.append(shape) or True)

    def run(ctx):
        fn = lambda p, x: op.forward(p, [x] * 3, ctx)[0]  # noqa: E731
        return jax.jit(fn)(params, xin), str(jax.make_jaxpr(fn)(params, xin))

    one, text_one = run(OpContext(training=False))
    assert "shard_map" not in text_one and seen[-1][:2] == (b, h)
    mesh = make_mesh((2, 2), ("data", "model"), jax.devices()[:4])
    strategy = types.SimpleNamespace(mesh_axis_for={
        "sample": "data", "head": "model"}.get)
    four, text_four = run(OpContext(training=False, mesh=mesh,
                                    op_strategy=strategy))
    assert "shard_map" in text_four
    assert seen[-1][:2] == (b // 2, h // 2)      # the shard's shapes
    np.testing.assert_allclose(np.asarray(four), np.asarray(one),
                               rtol=2e-5, atol=2e-5)
    # a head count the axis does not divide stays whole on that axis
    odd = types.SimpleNamespace(mesh_axis_for={
        "sample": "data", "head": "pipe"}.get)
    mesh3 = make_mesh((2, 3), ("data", "pipe"), jax.devices()[:6])
    run(OpContext(training=False, mesh=mesh3, op_strategy=odd))
    assert seen[-1][:2] == (b // 2, h)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk", [(128, 128), (128, 256), (256, 128)])
def test_flash_forward_matches_xla(rng, causal, sq, sk):
    b, h, d = 2, 2, 64
    q = jnp.asarray(rng.randn(b, sq, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, sk, h, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, sk, h, d).astype(np.float32))
    out = flash_attention_bshd(q, k, v, causal=causal, interpret=True)
    ref = xla_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk,d", [(128, 128, 64), (128, 256, 64),
                                     (256, 128, 64), (128, 128, 32)])
def test_flash_grads_match_xla(rng, causal, sq, sk, d):
    b, h = 2, 2
    q = jnp.asarray(rng.randn(b, sq, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, sk, h, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, sk, h, d).astype(np.float32))

    def loss_flash(q, k, v):
        o = flash_attention_bshd(q, k, v, causal=causal, interpret=True)
        return jnp.sum(jnp.sin(o.astype(jnp.float32)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(xla_attention(q, k, v, causal)
                               .astype(jnp.float32)))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-3, atol=2e-3,
                                   err_msg=f"d{name} mismatch")


def test_flash_head_dim_padding(rng):
    # two 32-wide heads fill half a slab (two zero heads pad it) and a
    # head_dim of 96 pads to 128 lanes; both paddings must be exact
    assert fa._lane_pad(2, 32) == (4, 32) and fa._lane_pad(2, 96) == (2, 128)
    assert fa._lane_pad(32, 64) == (32, 64) and fa._lane_pad(3, 64) == (4, 64)
    for h, d in ((2, 32), (2, 96)):
        q, k, v = qkv(rng, 1, 128, 128, h, d)
        np.testing.assert_allclose(
            np.asarray(flash_attention_bshd(q, k, v, interpret=True)),
            np.asarray(xla_attention(q, k, v, False)), rtol=2e-4, atol=2e-4)


def test_flash_bf16(rng):
    b, s, h, d = 2, 128, 2, 64
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    out = flash_attention_bshd(q, k, v, causal=True, interpret=True)
    ref = xla_attention(q, k, v, True)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=5e-2, atol=5e-2)


def test_flash_unpadded_lanes_matches_xla(rng):
    # d=64 is never padded to 128 lanes now: two heads share a slab, and
    # the packed arrays the kernels see are the inputs' own bytes
    b, s, h, d = 1, 128, 2, 64
    q = jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))
    assert fa._lane_pad(h, d) == (h, d)
    text = jax.jit(functools.partial(flash_attention_bshd, causal=True,
                                     interpret=True)).lower(q, q, q).as_text()
    assert "stablehlo.pad" not in text
    out = flash_attention_bshd(q, q, q, causal=True, interpret=True)
    ref = xla_attention(q, q, q, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_fused_qkv_under_remat_matches_no_remat():
    """The fused self-attention QKV projection is decided at GRAPH level
    (same tensor wired to q/k/v), so remat — which re-flattens the
    duplicated runtime leaves into distinct tracers — must not change
    the path or the numerics (review regression, r3)."""
    import numpy as np
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer

    def build(remat):
        cfg = FFConfig()
        cfg.batch_size = 4
        cfg.remat = remat
        ff = FFModel(cfg)
        x = ff.create_tensor((4, 8, 32), name="input")
        a = ff.multihead_attention(x, x, x, 32, 4, name="attn")
        t = ff.add(a, x)
        t = ff.reshape(t, (4, 8 * 32))
        ff.softmax(ff.dense(t, 4))
        ff.compile(optimizer=SGDOptimizer(lr=0.05),
                   loss_type="sparse_categorical_crossentropy",
                   metrics=[])
        return ff

    ff1, ff2 = build(False), build(True)
    attn = next(o for o in ff1.ops if o.op_type == "multihead_attention")
    assert attn._fused_qkv
    for name in ("attn", "dense"):
        ff2.set_weights(name, ff1.get_weights(name))
    rng = np.random.RandomState(0)
    b = {"input": rng.randn(4, 8, 32).astype(np.float32),
         "label": rng.randint(0, 4, 4).astype(np.int32)}
    for _ in range(3):
        l1 = float(ff1.train_batch(b)["loss"])
        l2 = float(ff2.train_batch(b)["loss"])
        np.testing.assert_allclose(l1, l2, rtol=1e-5)


def test_fused_kv_cross_attention_matches_separate():
    """Cross-attention with k is v (seq2seq decoder over encoder
    output) uses the fused 2x-wide KV projection; numerics must equal
    a graph where k and v are distinct tensors with identical values."""
    import numpy as np
    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer

    def build(share_kv):
        cfg = FFConfig()
        cfg.batch_size = 4
        ff = FFModel(cfg)
        q = ff.create_tensor((4, 6, 32), name="q")
        kv = ff.create_tensor((4, 9, 32), name="kv")
        if share_kv:
            a = ff.multihead_attention(q, kv, kv, 32, 4, name="xattn")
        else:
            kv2 = ff.create_tensor((4, 9, 32), name="kv2")
            a = ff.multihead_attention(q, kv, kv2, 32, 4, name="xattn")
        t = ff.reshape(a, (4, 6 * 32))
        ff.softmax(ff.dense(t, 4, name="head"))
        ff.compile(optimizer=SGDOptimizer(lr=0.05),
                   loss_type="sparse_categorical_crossentropy",
                   metrics=[])
        return ff

    ff1, ff2 = build(True), build(False)
    attn1 = next(o for o in ff1.ops if o.op_type == "multihead_attention")
    attn2 = next(o for o in ff2.ops if o.op_type == "multihead_attention")
    assert attn1._fused_kv and not attn1._fused_qkv
    assert not attn2._fused_kv
    for name in ("xattn", "head"):
        ff2.set_weights(name, ff1.get_weights(name))
    rng = np.random.RandomState(0)
    qv = rng.randn(4, 6, 32).astype(np.float32)
    kvv = rng.randn(4, 9, 32).astype(np.float32)
    y = rng.randint(0, 4, 4).astype(np.int32)
    for _ in range(3):
        l1 = float(ff1.train_batch({"q": qv, "kv": kvv, "label": y})["loss"])
        l2 = float(ff2.train_batch({"q": qv, "kv": kvv, "kv2": kvv,
                                    "label": y})["loss"])
        np.testing.assert_allclose(l1, l2, rtol=1e-5)
