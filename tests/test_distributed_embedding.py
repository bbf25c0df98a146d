"""DistributedEmbedding: the executable form of the reference's
per-device table placement (DLRM strategies pin table i to GPU i,
examples/cpp/DLRM/strategies/dlrm_strategy.cc:1-50) — E vocab-complete
tables stacked on a `table` axis and sharded over the mesh."""

import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer, Strategy, make_mesh
from flexflow_tpu.models import build_dlrm
from flexflow_tpu.parallel.pconfig import OpStrategy
from flexflow_tpu.search.simulator import Simulator


def build_model(bs=16, tables=8, vocab=64, dim=8, mesh=None, strategy=None):
    cfg = FFConfig()
    cfg.batch_size = bs
    ff = FFModel(cfg, mesh=mesh, strategy=strategy)
    ins = [ff.create_tensor((bs, 2), dtype=jnp.int32, name=f"sparse_{i}")
           for i in range(tables)]
    embs = ff.distributed_embedding(ins, vocab, dim, aggr="sum",
                                    name="tables")
    t = ff.concat(embs, axis=1)
    t = ff.dense(t, 4)
    t = ff.softmax(t)
    ff.compile(optimizer=SGDOptimizer(lr=0.05),
               loss_type="sparse_categorical_crossentropy",
               metrics=["accuracy"], mesh=mesh, strategy=strategy)
    return ff


def data(bs=16, tables=8, vocab=64, seed=0):
    rng = np.random.RandomState(seed)
    batch = {f"sparse_{i}": rng.randint(0, vocab, (bs, 2)).astype(np.int32)
             for i in range(tables)}
    batch["label"] = rng.randint(0, 4, bs).astype(np.int32)
    return batch


def test_forward_matches_per_table_gather():
    ff = build_model()
    kern = np.random.RandomState(1).randn(8, 64, 8).astype(np.float32)
    ff.set_weights("tables", {"kernel": kern})
    batch = data()
    logits_in = {k: v for k, v in batch.items() if k != "label"}
    # spot-check through the op itself: output e must equal table e's bag
    op = ff.ops[0]
    from flexflow_tpu.op import OpContext
    outs = op.forward({"kernel": jnp.asarray(kern)},
                      [jnp.asarray(logits_in[f"sparse_{i}"])
                       for i in range(8)], OpContext(training=False))
    for e in range(8):
        expect = kern[e][batch[f"sparse_{e}"]].sum(axis=1)
        np.testing.assert_allclose(np.asarray(outs[e]), expect, rtol=1e-5)
    # and the whole model runs
    m = ff.train_batch(batch)
    assert np.isfinite(float(m["loss"]))


def test_table_sharded_matches_unsharded():
    batch = data()
    ff1 = build_model()
    kern = np.asarray(ff1.get_weights("tables")["kernel"])

    mesh = make_mesh((1, 8), ("data", "model"))
    strat = Strategy(default=OpStrategy({"sample": "data"}))
    strat.set("tables", OpStrategy({"sample": "data", "table": "model"}))
    ff2 = build_model(mesh=mesh, strategy=strat)
    ff2.set_weights("tables", {"kernel": kern})
    ff2.set_weights("dense", ff1.get_weights("dense"))

    w = ff2.state.params["tables"]["kernel"]
    assert w.sharding.spec == P("model"), w.sharding.spec

    m1 = ff1.train_batch(batch)
    m2 = ff2.train_batch(batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4


def test_stacked_dlrm_trains_table_sharded():
    cfg = FFConfig()
    cfg.batch_size = 32
    mesh = make_mesh((1, 8), ("data", "model"))
    strat = Strategy(default=OpStrategy({"sample": "data"}))
    strat.set("emb_tables", OpStrategy({"sample": "data",
                                        "table": "model"}))
    ff = build_dlrm(cfg, batch_size=32,
                    embedding_vocab_sizes=(256,) * 8,
                    mesh=mesh, strategy=strat, stacked_tables=True)
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type="mean_squared_error", metrics=[],
               mesh=mesh, strategy=strat)
    rng = np.random.RandomState(0)
    batch = {"dense_features": rng.randn(32, 13).astype(np.float32),
             "label": (rng.rand(32, 1) > 0.5).astype(np.float32)}
    for i in range(8):
        batch[f"sparse_{i}"] = rng.randint(0, 256, (32, 1)).astype(np.int32)
    m = ff.train_batch(batch)
    assert np.isfinite(float(m["loss"]))


def test_cost_model_prefers_table_sharding():
    """Simulated: table sharding (concurrent vocab-complete lookups + an
    all-gather) must beat vocab sharding (a psum per step), and beat
    replication when the replicated tables exceed HBM (the memory
    penalty, simulator.cc:603-628 analog — which is WHY the reference
    places DLRM tables per-device; with row-level traffic pricing,
    replication of tables that FIT is legitimately free of collectives
    and wins on speed)."""
    cfg = FFConfig()
    cfg.batch_size = 1024
    cfg.enable_parameter_parallel = True
    ff = FFModel(cfg)
    ins = [ff.create_tensor((1024, 1), dtype=jnp.int32, name=f"s{i}")
           for i in range(8)]
    # 8 x 10M x 64 f32 = 20GB replicated (+optimizer state) >> one
    # chip's HBM; sharded over 8 devices it fits
    embs = ff.distributed_embedding(ins, 10_000_000, 64, name="tables")
    t = ff.concat(embs, axis=1)
    t = ff.softmax(ff.dense(t, 4))
    mesh = make_mesh((1, 8), ("data", "model"))
    sim = Simulator(ff, mesh)

    def strat(extra):
        s = Strategy()
        s.set("tables", OpStrategy({**extra}))
        return s

    t_table = sim.simulate(strat({"table": "model"}))
    t_vocab = sim.simulate(strat({"vocab": "model"}))
    t_repl = sim.simulate(strat({}))
    assert t_table < t_vocab, (t_table, t_vocab)
    assert t_table < t_repl, (t_table, t_repl)


def test_cost_model_ignores_non_dividing_table_axis():
    """6 tables on a 4-wide axis: the executor's spec_for_axes drops the
    non-dividing axis (weight stays replicated), so the cost model must
    price it as replication rather than a phantom 4x speedup."""
    cfg = FFConfig()
    cfg.batch_size = 64
    ff = FFModel(cfg)
    ins = [ff.create_tensor((64, 1), dtype=jnp.int32, name=f"s{i}")
           for i in range(6)]
    embs = ff.distributed_embedding(ins, 10_000, 64, name="tables")
    t = ff.concat(embs, axis=1)
    t = ff.softmax(ff.dense(t, 4))
    mesh = make_mesh((2, 4), ("data", "model"))
    sim = Simulator(ff, mesh)
    s_table = Strategy()
    s_table.set("tables", OpStrategy({"table": "model"}))
    s_repl = Strategy()
    s_repl.set("tables", OpStrategy({}))
    assert sim.simulate(s_table) == sim.simulate(s_repl)


def test_table_sharded_finite_on_combined_mesh():
    """Regression (ROADMAP open item, fixed this PR): on a mesh carrying
    a third axis (the combined dryrun mesh data2 x model2 x seq2) with
    `table` GENUINELY sharded (tables %% axis == 0), the jitted train
    step hit loss=nan. Root cause: jnp.take's default out-of-bounds
    mode is "fill" (NaN fill), and GSPMD's partitioning of the
    table-sharded gather rewrites global indices into locally-shifted
    ones, so the fill-validity select fired on in-bounds lookups —
    forward lookups came back NaN only when XLA actually partitioned
    the gather (a 2-axis mesh replicated it and masked the bug). The
    gathers now use mode="clip" (XLA's native clamp semantics).

    The combined-mesh dryrun graph shape on CPU: 3-D activations, a
    broadcast embedding bias, table+vocab+channel_out all mapped."""
    mesh = make_mesh((2, 2, 2), ("data", "model", "seq"))
    strategy = Strategy(default=OpStrategy({
        "sample": "data", "head": "model", "channel_out": "model",
        "vocab": "model", "seq": "seq", "table": "model"}))
    batch, seq_len, hidden = 8, 16, 64
    cfg = FFConfig()
    cfg.batch_size = batch
    ff = FFModel(cfg, mesh=mesh, strategy=strategy)
    x = ff.create_tensor((batch, seq_len, hidden), name="input")
    sparse = [ff.create_tensor((batch, 1), dtype=jnp.int32,
                               name=f"cat_{i}") for i in range(2)]
    embs = ff.distributed_embedding(sparse, 32, hidden, name="cat_tables")
    bias = ff.add(embs[0], embs[1], name="bias_sum")
    bias = ff.reshape(bias, (batch, 1, hidden), name="cat_bias")
    t = ff.add(x, bias, name="res")
    head, _ = ff.split(t, [1, seq_len - 1], axis=1, name="cls_split")
    head = ff.reshape(head, (batch, hidden), name="cls_reshape")
    ff.softmax(ff.dense(head, 10, name="cls_head"), name="sm")
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type="sparse_categorical_crossentropy", metrics=[])
    rng = np.random.RandomState(0)
    bd = {"input": rng.randn(batch, seq_len, hidden).astype(np.float32),
          "label": rng.randint(0, 10, (batch,)).astype(np.int32)}
    for i in range(2):
        bd[f"cat_{i}"] = rng.randint(0, 32, (batch, 1)).astype(np.int32)
    losses = [float(ff.train_batch(bd)["loss"]) for _ in range(2)]
    assert np.isfinite(losses).all(), losses
    # and the lookups are REAL (not clamp-degenerate): match the
    # unsharded reference forward
    ref = FFModel(FFConfig(batch_size=batch))
    xr = ref.create_tensor((batch, seq_len, hidden), name="input")
    sr = [ref.create_tensor((batch, 1), dtype=jnp.int32, name=f"cat_{i}")
          for i in range(2)]
    er = ref.distributed_embedding(sr, 32, hidden, name="cat_tables")
    br = ref.add(er[0], er[1], name="bias_sum")
    br = ref.reshape(br, (batch, 1, hidden), name="cat_bias")
    tr = ref.add(xr, br, name="res")
    hr, _ = ref.split(tr, [1, seq_len - 1], axis=1, name="cls_split")
    hr = ref.reshape(hr, (batch, hidden), name="cls_reshape")
    ref.softmax(ref.dense(hr, 10, name="cls_head"), name="sm")
    ref.compile(optimizer=SGDOptimizer(lr=0.01),
                loss_type="sparse_categorical_crossentropy", metrics=[])
    ref.set_weights("cat_tables",
                    {"kernel": ff.get_weights("cat_tables")["kernel"]})
    ref.set_weights("cls_head", ff.get_weights("cls_head"))
    l_ref = float(ref.train_batch(bd)["loss"])
    l_sharded = float(ff.train_batch(bd)["loss"])
    assert np.isfinite(l_ref)
    np.testing.assert_allclose(l_sharded, l_ref, rtol=1e-4)
    # the step's UPDATE half too: the sparse row scatter is the op that
    # mis-partitioned under jax 0.9.0 (as vmap(sparse_update), a batched
    # scatter over the table-sharded operand — core/executor.py
    # _apply_update now runs one flat scatter), so the tables after the
    # step must equal the unsharded ones, not just the loss before it
    np.testing.assert_allclose(
        ff.get_weights("cat_tables")["kernel"],
        ref.get_weights("cat_tables")["kernel"], rtol=1e-5, atol=1e-6)
