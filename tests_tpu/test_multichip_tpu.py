"""The same path on four chips (one v5e host, a 2x2 mesh): what the CPU
suite holds on eight virtual devices, held on real ones.

  * the trainer on a 2 x 2 data x model mesh reproduces the one-chip
    loss at the CPU suite's tolerance (tests/test_parallel.py: 1e-3);
  * `dryrun_multichip(4)` — every parallel axis — runs;
  * `ServeEngine(tensor_parallel=4)` is token-for-token the one-chip
    engine on f32 (PR 9's contract);
  * a ReplicaPool of four one-chip replicas puts each on its own chip,
    and a fifth is refused with both numbers.

f32 means f32 here: XLA's default precision for an f32 dot on a TPU is
one bf16 pass, under which a sharded contraction and an unsharded one
legitimately differ in the third digit. Every comparison runs under
`default_matmul_precision("highest")`, the CPU suite's setting
(tests/conftest.py).

Skipped under four devices: `chiprun --chips 4 -- python -m pytest
tests_tpu/test_multichip_tpu.py -q`.
"""

import jax
import numpy as np
import pytest

from flexflow_tpu import FFConfig, SGDOptimizer, make_mesh
from flexflow_tpu.models.transformer import build_transformer_lm
from flexflow_tpu.parallel.pconfig import megatron_strategy

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs a four-chip host")


@pytest.fixture(autouse=True)
def _f32_means_f32():
    with jax.default_matmul_precision("highest"):
        yield


def _lm(mesh=None, strategy=None, **cfg_kw):
    cfg = FFConfig(batch_size=4, kv_page_size=16, kv_num_pages=65,
                   serve_max_seqs=4, serve_prefill_budget=64, **cfg_kw)
    return build_transformer_lm(cfg, vocab_size=512, max_seq_len=128,
                                hidden=256, num_heads=4, num_layers=2,
                                ff_dim=512, mesh=mesh, strategy=strategy)


def test_trainer_on_2x2_mesh_matches_one_chip():
    import functools

    from flexflow_tpu.core.losses import sparse_categorical_crossentropy
    loss = functools.partial(sparse_categorical_crossentropy,
                             from_logits=True)
    rng = np.random.RandomState(0)
    tokens = rng.randint(1, 512, (4, 128)).astype(np.int32)
    batch = {"tokens": tokens,
             "positions": np.tile(np.arange(128, dtype=np.int32), (4, 1)),
             "label": np.roll(tokens, -1, axis=1)}

    def losses(ff):
        ff.compile(optimizer=SGDOptimizer(lr=0.05), loss_type=loss,
                   metrics=[])
        return [float(ff.train_batch(batch)["loss"]) for _ in range(3)]

    one = losses(_lm())
    mesh = make_mesh((2, 2), ("data", "model"))
    ff = _lm(mesh=mesh, strategy=megatron_strategy())
    four = losses(ff)
    on = {d.id for leaf in jax.tree_util.tree_leaves(ff.state.params)
          for d in leaf.devices()}
    assert on == {d.id for d in jax.devices()[:4]}
    print(f"\nloss, one chip:  {one}\nloss, 2x2 mesh: {four}")
    assert np.isfinite(four).all() and four[-1] < four[0]
    np.testing.assert_allclose(four, one, atol=1e-3, rtol=0)


def test_tensor_parallel_4_is_token_identical_on_f32():
    from flexflow_tpu.serve import ServeEngine
    ff = _lm()
    rng = np.random.RandomState(1)
    prompts = [list(rng.randint(1, 512, size=n))
               for n in (3, 17, 40, 90, 17, 5)]     # 90 > budget: chunks
    one = ServeEngine(ff)
    ref = one.generate(prompts, 12)
    tp4 = ServeEngine(ff, tensor_parallel=4)
    warm = dict(tp4.warmup())
    assert tp4.devices == tuple(jax.devices()[:4])
    assert tp4.generate(prompts, 12) == ref
    assert tp4.generate(prompts, 12) == ref          # warm: prefix hits
    assert tp4.compile_counts() == warm
    assert tp4.attn_impl == one.attn_impl == "pallas"
    tp4.cache.check_invariants()


def test_pool_of_four_replicas_owns_four_chips():
    from flexflow_tpu.serve import ReplicaPool, TrafficSpec, make_traffic
    from flexflow_tpu.utils.profiling import router_report
    ff = _lm(serve_spec_decode=False)
    devs = jax.devices()
    with ReplicaPool(ff, num_replicas=4, policy="round_robin") as pool:
        assert [r.engine.devices for r in pool.replicas] == [
            (d,) for d in devs[:4]]
        for r in pool.replicas:
            on = {d for leaf in jax.tree_util.tree_leaves(
                (r.engine._step_params, r.engine.pool))
                for d in leaf.devices()}
            assert on == set(r.engine.devices)
        traffic = make_traffic(TrafficSpec(
            requests=16, seed=0, rate_rps=200.0, tenants=2,
            prefix_tokens=32, tail_mean=8.0, output_mean=6.0,
            max_prompt=96, max_new_cap=8, vocab=512))
        res = pool.run(traffic)
        assert res["completed"] == 16
        assert all(p["steps"] > 0 for p in res["per_replica"])
        pool.assert_zero_recompiles()
        pool.check_drained()
        report = router_report(res, pool.metrics)
        for i in range(4):
            assert f"[{devs[i].id}]" in report
        print(report)
    if len(devs) == 4:
        # a fifth replica has no chip: the engine a pool would build
        # for it is refused with both numbers (before it compiles)
        from flexflow_tpu.serve import ServeEngine
        with pytest.raises(ValueError, match=r"chips \[4, 5\).*has 4"):
            ServeEngine(ff, replica=4)


def test_dryrun_multichip_on_four_chips(capsys):
    import __graft_entry__ as g
    g.dryrun_multichip(4)
    out = capsys.readouterr().out
    assert out.count(" OK") >= 4, out
    print(out)
