"""Mixed-precision policy (FFConfig.compute_dtype / param_dtype).

What the policy promises (docs/performance.md):
  * bf16-vs-f32 LOSS PARITY within tolerance on transformer + DLRM —
    f32 master weights keep the walk on the f32 trajectory;
  * master params and optimizer state VERIFIABLY stay f32 while
    step-internal activations/params run at compute_dtype;
  * flash attention takes bf16 inputs with f32 LSE/accumulation on
    both the pallas-interpret and jnp paths;
  * the cost stack prices dtypes (per-dtype peak, itemsize bytes) and
    the persistent cost cache MISSES on a precision flip.
"""

import numpy as np
import pytest

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402

from flexflow_tpu import FFConfig, FFModel  # noqa: E402
from flexflow_tpu.core.optimizers import AdamOptimizer  # noqa: E402
from flexflow_tpu.models.dlrm import build_dlrm  # noqa: E402
from flexflow_tpu.models.transformer import build_transformer  # noqa: E402

PARITY_TOL = 0.05  # relative to the running loss (see tools/mp_bench.py)


def small_transformer(compute_dtype, **cfg_kw):
    cfg = FFConfig(batch_size=8)
    cfg.compute_dtype = compute_dtype
    for k, v in cfg_kw.items():
        setattr(cfg, k, v)
    ff = build_transformer(cfg, batch_size=8, seq_len=32, hidden=64,
                           num_heads=4, num_layers=2, ff_dim=128,
                           num_classes=10, layer_norm=True)
    ff.compile(loss_type="sparse_categorical_crossentropy", metrics=[])
    return ff


def transformer_batch(seed=0):
    rng = np.random.RandomState(seed)
    return {"input": rng.randn(8, 32, 64).astype(np.float32),
            "label": rng.randint(0, 10, 8).astype(np.int32)}


def train_curve(ff, batch, steps=8):
    out = [float(ff.train_batch(batch)["loss"]) for _ in range(steps)]
    assert all(np.isfinite(out)), out
    return out


def assert_f32_masters(ff):
    for leaf in jax.tree_util.tree_leaves(ff.state.params):
        assert str(leaf.dtype) == "float32", leaf.dtype
    for leaf in jax.tree_util.tree_leaves(ff.state.opt_state):
        assert str(leaf.dtype) == "float32", leaf.dtype


# ---------------------------------------------------------------- parity

def test_transformer_bf16_parity_and_f32_masters():
    batch = transformer_batch()
    cf = train_curve(small_transformer("float32"), batch)
    ffb = small_transformer("bfloat16")
    cb = train_curve(ffb, batch)
    assert_f32_masters(ffb)
    for a, b in zip(cf, cb):
        assert abs(a - b) <= PARITY_TOL * max(1.0, abs(a)), (cf, cb)
    # training actually happened (not two flat curves agreeing)
    assert cb[-1] < cb[0] - 0.5


def test_dlrm_bf16_parity_sparse_embeddings():
    """DLRM exercises the sparse-embedding row-update path: the row
    gather feeds bf16 forward, row grads scatter into the f32 master
    table."""
    rng = np.random.RandomState(0)
    batch = {"dense_features": rng.randn(32, 13).astype(np.float32),
             "label": rng.randint(0, 2, (32, 1)).astype(np.float32)}
    for i in range(8):
        batch[f"sparse_{i}"] = rng.randint(0, 1000, (32, 1)).astype(
            np.int32)

    def build(dt):
        cfg = FFConfig(batch_size=32)
        cfg.compute_dtype = dt
        ff = build_dlrm(cfg, batch_size=32,
                        embedding_vocab_sizes=(1000,) * 8)
        ff.compile(loss_type="binary_crossentropy", metrics=[])
        assert ff.executor._sparse_table_ops(), \
            "sparse-update path must be active for this test"
        return ff

    cf = train_curve(build("float32"), batch)
    ffb = build("bfloat16")
    cb = train_curve(ffb, batch)
    assert_f32_masters(ffb)
    for a, b in zip(cf, cb):
        assert abs(a - b) <= PARITY_TOL * max(1.0, abs(a)), (cf, cb)


def test_adam_masters_stay_f32_under_bf16():
    cfg = FFConfig(batch_size=8)
    cfg.compute_dtype = "bfloat16"
    ff = build_transformer(cfg, batch_size=8, seq_len=16, hidden=32,
                           num_heads=2, num_layers=1, ff_dim=64,
                           num_classes=4, layer_norm=True)
    ff.compile(optimizer=AdamOptimizer(lr=1e-3),
               loss_type="sparse_categorical_crossentropy", metrics=[])
    rng = np.random.RandomState(0)
    batch = {"input": rng.randn(8, 16, 32).astype(np.float32),
             "label": rng.randint(0, 4, 8).astype(np.int32)}
    for _ in range(3):
        ff.train_batch(batch)
    assert_f32_masters(ff)
    # Adam's m/v advanced (they are live f32 state, not dead zeros)
    m_norm = sum(float(jnp.abs(a).sum()) for a in
                 jax.tree_util.tree_leaves(ff.state.opt_state["m"]))
    assert m_norm > 0.0


# --------------------------------------------- step-internal activations

def test_step_internals_run_at_compute_dtype():
    """forward_values (the walked graph inside every jitted step) casts
    master params + float inputs down, so intermediate tensor values
    carry compute_dtype."""
    ff = small_transformer("bfloat16")
    ex = ff.executor
    batch = ex.shard_batch(transformer_batch())
    # the loader-side cast already happened: declared float inputs are
    # compute-dtype on device
    assert batch["input"].dtype == jnp.bfloat16
    values, _ = ex.forward_values(ff.state.params, ff.state.states,
                                  batch, training=False, rng=None)
    float_dts = {str(v.dtype) for v in values.values()
                 if jnp.issubdtype(v.dtype, jnp.floating)}
    assert float_dts == {"bfloat16"}, float_dts
    # while the masters it read stayed f32
    assert_f32_masters(ff)

    # embedding-bearing graph: Embedding pins an out_dtype (f32 by
    # default) — the walk must keep the value stream at compute_dtype
    # or everything downstream of a table silently upcasts
    cfg = FFConfig(batch_size=8)
    cfg.compute_dtype = "bfloat16"
    ffd = build_dlrm(cfg, batch_size=8,
                     embedding_vocab_sizes=(100,) * 4)
    ffd.compile(loss_type="binary_crossentropy", metrics=[])
    rng = np.random.RandomState(0)
    batch = {"dense_features": rng.randn(8, 13).astype(np.float32)}
    for i in range(4):
        batch[f"sparse_{i}"] = rng.randint(0, 100, (8, 1)).astype(
            np.int32)
    batch = ffd.executor.shard_batch(batch)
    values, _ = ffd.executor.forward_values(
        ffd.state.params, ffd.state.states, batch, training=False,
        rng=None)
    float_dts = {str(v.dtype) for v in values.values()
                 if jnp.issubdtype(v.dtype, jnp.floating)}
    assert float_dts == {"bfloat16"}, float_dts


def test_declared_input_dtypes_follow_policy():
    ff32 = small_transformer("float32")
    ffb = small_transformer("bfloat16")
    assert ff32.executor.declared_input_dtypes["input"] == jnp.float32
    assert ffb.executor.declared_input_dtypes["input"] == jnp.bfloat16


def test_bn_statistics_stay_f32_under_bf16():
    cfg = FFConfig(batch_size=8)
    cfg.compute_dtype = "bfloat16"
    ff = FFModel(cfg)
    x = ff.create_tensor((8, 4, 8, 8), name="input")
    t = ff.conv2d(x, 8, 3, 3, 1, 1, 1, 1, name="c0")
    t = ff.batch_norm(t, name="bn0")
    t = ff.flat(t, name="flat")
    t = ff.dense(t, 4, name="head")
    ff.softmax(t, name="sm")
    ff.compile(loss_type="sparse_categorical_crossentropy", metrics=[])
    rng = np.random.RandomState(0)
    batch = {"input": rng.randn(8, 4, 8, 8).astype(np.float32),
             "label": rng.randint(0, 4, 8).astype(np.int32)}
    ff.train_batch(batch)
    bn = ff.state.states["bn0"]
    assert str(bn["running_mean"].dtype) == "float32"
    assert str(bn["running_var"].dtype) == "float32"
    # and the stats moved off their init values
    assert float(jnp.abs(bn["running_mean"]).sum()) > 0.0


# ------------------------------------------------------------- pipelines

@pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
def test_pipeline_bf16_parity(schedule):
    """GPipe and 1F1B over a dp2 x pp2 mesh: packed rows stay f32
    masters, the wire carries bf16 activations, losses track f32."""
    from flexflow_tpu import make_mesh

    def build(dt):
        cfg = FFConfig(batch_size=16)
        cfg.compute_dtype = dt
        cfg.pipeline_stages = 2
        cfg.pipeline_microbatches = 4
        cfg.pipeline_schedule = schedule
        mesh = make_mesh((2, 2), ("data", "pipe"))
        ff = FFModel(cfg, mesh=mesh)
        x = ff.create_tensor((16, 32), name="input")
        t = ff.dense(x, 64, activation="relu", name="fc1")
        t = ff.dense(t, 64, activation="relu", name="fc2")
        t = ff.dense(t, 48, activation="relu", name="fc3")
        t = ff.dense(t, 10, name="fc4")
        ff.softmax(t, name="sm")
        ff.compile(loss_type="sparse_categorical_crossentropy",
                   metrics=[])
        return ff

    rng = np.random.RandomState(0)
    batch = {"input": rng.randn(16, 32).astype(np.float32),
             "label": rng.randint(0, 10, 16).astype(np.int32)}
    cf = train_curve(build("float32"), batch, steps=3)
    ffb = build("bfloat16")
    cb = train_curve(ffb, batch, steps=3)
    for a, b in zip(cf, cb):
        assert abs(a - b) <= PARITY_TOL * max(1.0, abs(a)), (cf, cb)
    # packed master rows stay f32
    from flexflow_tpu.core.staged import PACKED
    for a in ffb.state.params[PACKED].values():
        assert str(a.dtype) == "float32"


def test_pipeline_wire_carries_compute_dtype():
    from flexflow_tpu.parallel.graph_pipeline import (_wire_layouts,
                                                      balanced_stages,
                                                      build_stage_plan)
    cfg = FFConfig(batch_size=8)
    cfg.compute_dtype = "bfloat16"
    ff = FFModel(cfg)
    x = ff.create_tensor((8, 16), name="input")
    t = ff.dense(x, 16, name="a")
    t = ff.dense(t, 16, name="b")
    ff.softmax(t, name="sm")
    plan = build_stage_plan(ff, balanced_stages(ff, 2))
    _, widths = _wire_layouts(plan, ff)
    assert set(widths) == {"bfloat16"}, widths
    # and without a policy the wire stays at the declared dtype
    cfg2 = FFConfig(batch_size=8)
    ff2 = FFModel(cfg2)
    x = ff2.create_tensor((8, 16), name="input")
    t = ff2.dense(x, 16, name="a")
    t = ff2.dense(t, 16, name="b")
    ff2.softmax(t, name="sm")
    plan2 = build_stage_plan(ff2, balanced_stages(ff2, 2))
    _, widths2 = _wire_layouts(plan2, ff2)
    assert set(widths2) == {"float32"}, widths2


# -------------------------------------------------------- flash attention

def _mha_ref(q, k, v):
    s = jnp.einsum("bihd,bjhd->bhij", q.astype(jnp.float32),
                   k.astype(jnp.float32))
    s = s / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhij,bjhd->bihd", p, v.astype(jnp.float32))


@pytest.mark.parametrize("impl", ["interpret", "jnp"])
def test_flash_attention_bf16_fwd_bwd(impl):
    """bf16 q/k/v through both implementations: f32 LSE/accumulation
    keeps the result within bf16 tolerance of the f32 reference, and
    jax.grad works (the bwd kernels recompute from the f32 logsumexp)."""
    from flexflow_tpu.kernels.flash_attention import flash_attention_bshd

    rng = np.random.RandomState(0)
    b, s, h, d = 2, 128, 2, 32
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.bfloat16)

    if impl == "interpret":
        def f(q, k, v):
            return flash_attention_bshd(q, k, v, causal=False,
                                        interpret=True)
    else:
        # the executor's non-pallas path: XLA einsum attention with f32
        # softmax statistics — what ops/attention.py runs off-TPU
        def f(q, k, v):
            return _mha_ref(q, k, v).astype(q.dtype)

    o = f(q, k, v)
    assert o.dtype == jnp.bfloat16
    ref = _mha_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)

    def loss(q, k, v):
        return jnp.sum(f(q, k, v).astype(jnp.float32))

    gq, gk, gv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in (gq, gk, gv):
        assert g.dtype == jnp.bfloat16
        assert bool(jnp.all(jnp.isfinite(g.astype(jnp.float32))))
    # grads match the f32-reference gradient at bf16 tolerance
    gq32 = jax.grad(lambda q_: jnp.sum(_mha_ref(q_, k, v)))(q)
    np.testing.assert_allclose(np.asarray(gq, np.float32),
                               np.asarray(gq32, np.float32),
                               atol=6e-2, rtol=6e-2)


def test_paged_attention_bf16_pallas_vs_jnp():
    """The serving kernels accept bf16 queries against (f32) KV pages:
    interpret-pallas and jnp fallback agree bit-for-bit."""
    from flexflow_tpu.kernels.paged_ragged_v2 import \
        paged_attention_ragged_v2

    rng = np.random.RandomState(1)
    P, ps, hh, d = 9, 8, 2, 16
    B, pp = 3, 4
    q = jnp.asarray(rng.randn(B, hh, d), jnp.bfloat16)
    kp = jnp.asarray(rng.randn(P, ps, hh, d), jnp.float32)
    vp = jnp.asarray(rng.randn(P, ps, hh, d), jnp.float32)
    pt = jnp.asarray(rng.randint(1, P, (B, pp)), jnp.int32)
    sl = jnp.asarray([5, 17, 30], jnp.int32)
    slots = jnp.arange(B, dtype=jnp.int32)     # one lane per sequence
    a = paged_attention_ragged_v2(q, kp, vp, pt, slots, sl,
                                  use_pallas=True, interpret=True)
    b_ = paged_attention_ragged_v2(q, kp, vp, pt, slots, sl,
                                   use_pallas=False)
    assert a.dtype == jnp.bfloat16 and b_.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b_, np.float32))


# ------------------------------------------------------------ cost stack

def test_machine_model_prices_dtypes():
    from flexflow_tpu.search.machine_model import default_machine_model

    mm = default_machine_model(None)
    flops = 1e12
    t_bf16 = mm.compute_time(flops, 0.0, dtype="bfloat16")
    t_f32 = mm.compute_time(flops, 0.0, dtype="float32")
    assert t_f32 == pytest.approx(2.0 * t_bf16)
    # legacy callers (dtype=None) keep the bf16-basis peak
    assert mm.compute_time(flops, 0.0) == pytest.approx(t_bf16)
    # a measured per-dtype factor overrides the family factor
    mm.efficiency["matmul:float32"] = 2 * mm.efficiency["matmul"]
    assert mm.compute_time(flops, 0.0, dtype="float32") == \
        pytest.approx(t_bf16)


def test_op_cost_dtype_aware():
    """bf16 policy halves a linear op's compute time (2x MXU rate) and
    its HBM/collective bytes; the DP grad sync stays at the f32 param
    dtype."""
    from flexflow_tpu import make_mesh
    from flexflow_tpu.parallel.pconfig import OpStrategy
    from flexflow_tpu.search.cost_model import op_cost
    from flexflow_tpu.search.machine_model import default_machine_model

    def linear_cost(dt):
        cfg = FFConfig(batch_size=256)
        cfg.compute_dtype = dt
        ff = FFModel(cfg)
        x = ff.create_tensor((256, 1024), name="input")
        ff.dense(x, 1024, name="fc")
        mesh = make_mesh((8,), ("data",))
        mm = default_machine_model(mesh)
        return op_cost(ff.ops[0], OpStrategy({"sample": "data"}), mesh,
                       mm)

    c32 = linear_cost("float32")
    cb = linear_cost("bfloat16")
    assert cb.fwd == pytest.approx(c32.fwd / 2, rel=1e-6)
    assert cb.bwd == pytest.approx(c32.bwd / 2, rel=1e-6)
    assert cb.sync == pytest.approx(c32.sync)  # f32 grads either way
    assert cb.mem < c32.mem  # bf16 activations


def test_cost_cache_misses_on_dtype_flip():
    """Regression for the cache-correctness satellite: the machine
    fingerprint folds in the precision policy, so entries written under
    f32 pricing can never be replayed into a bf16 search."""
    from flexflow_tpu.search.cost_cache import (CostCache,
                                                machine_fingerprint)
    from flexflow_tpu.search.cost_model import OpCost
    from flexflow_tpu.search.machine_model import default_machine_model

    mm = default_machine_model(None)
    fp32 = machine_fingerprint(mm, None,
                               precision=("float32", "float32"))
    fpb = machine_fingerprint(mm, None,
                              precision=("bfloat16", "float32"))
    assert fp32 != fpb
    cache = CostCache(path="/nonexistent/never-written.json")
    key = CostCache.entry_key("sig", ["axis"], ())
    cache.put(fp32, key, OpCost(fwd=1.0, bwd=2.0, fwd_comm=0.0,
                                bwd_comm=0.0, sync=0.0, mem=0.0))
    assert cache.get(fp32, key) is not None
    assert cache.get(fpb, key) is None  # dtype flip MUST miss


def test_simulator_fingerprint_separates_precision():
    from flexflow_tpu import make_mesh
    from flexflow_tpu.search.simulator import Simulator

    def fp(dt):
        cfg = FFConfig(batch_size=8)
        cfg.compute_dtype = dt
        ff = build_transformer(cfg, batch_size=8, seq_len=16, hidden=32,
                               num_heads=2, num_layers=1, ff_dim=64)
        sim = Simulator(ff, make_mesh((1,), ("data",)))
        return sim._fingerprint

    assert fp("float32") != fp("bfloat16")


# ------------------------------------------------------------ serve + IO

def test_serve_engine_bf16_exactness():
    from flexflow_tpu.serve.engine import ServeEngine
    from flexflow_tpu.models.transformer import build_transformer_lm

    cfg = FFConfig(batch_size=2)
    cfg.compute_dtype = "bfloat16"
    cfg.kv_page_size = 8
    cfg.kv_num_pages = 65
    cfg.serve_max_seqs = 2
    cfg.serve_prefill_budget = 32
    ff = build_transformer_lm(cfg, vocab_size=32, max_seq_len=32,
                              batch_size=2, hidden=32, num_heads=2,
                              num_layers=2, ff_dim=64)
    eng = ServeEngine(ff, use_pallas=False)
    assert eng.act_dtype == jnp.bfloat16
    eng.warmup()
    c0 = eng.compile_counts()
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, 32, n)) for n in (4, 9)]
    out = eng.generate(prompts, max_new_tokens=6)
    assert out == eng.generate_reference(prompts, max_new_tokens=6)
    assert eng.compile_counts() == c0  # zero recompiles after warmup


def test_host_to_device_casts_in_transfer():
    """Satellite: the single-host path builds the numpy array at the
    target dtype and device_puts ONCE straight to the sharding."""
    from flexflow_tpu import make_mesh
    from flexflow_tpu.core.dataloader import host_to_device
    from flexflow_tpu.parallel.sharding import batch_sharding

    mesh = make_mesh((1,), ("data",))
    host = np.random.RandomState(0).randn(8, 4).astype(np.float32)
    arr = host_to_device(host, mesh, dtype=jnp.bfloat16)
    assert arr.dtype == jnp.bfloat16
    assert arr.sharding == batch_sharding(mesh, 2)
    np.testing.assert_allclose(np.asarray(arr, np.float32), host,
                               atol=1e-2)
    # int dtype preserved with no cast requested
    ints = np.arange(8, dtype=np.int32)[:, None]
    arr = host_to_device(ints, mesh)
    assert arr.dtype == jnp.int32
    # meshless path unchanged
    arr = host_to_device(host, None, dtype=jnp.bfloat16)
    assert arr.dtype == jnp.bfloat16


def test_cli_flags_parse_dtypes():
    cfg = FFConfig(argv=["--compute-dtype", "bfloat16",
                         "--param-dtype", "float32"])
    assert cfg.compute_dtype == jnp.dtype(jnp.bfloat16)
    assert cfg.param_dtype == jnp.dtype(jnp.float32)
    with pytest.raises(ValueError):
        FFConfig(argv=["--compute-dtype", "int32"])
