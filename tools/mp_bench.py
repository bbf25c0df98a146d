"""Mixed-precision benchmark: bf16 compute path vs f32 (BENCH_mp.json).

Three measurements, mirroring what the policy claims
(FFConfig.compute_dtype/param_dtype, docs/performance.md):

  1. SIMULATED step-makespan reduction bf16-vs-f32 on the TPU machine
     model, for the transformer (compute-bound) and a DLRM with
     MLPerf-size MLPs (gather/sync-heavy — the honest harder case).
     Pure cost-model arithmetic (search/cost_model.py prices flops at
     the per-dtype MXU rate and bytes at the actual itemsize), so it
     gates on CPU like PR 2/3's algorithmic gates.
  2. NUMERICS PARITY: train the same model f32 and bf16 (f32 master
     weights either way) for N steps on identical data and pin the
     bf16 loss curve to the f32 one within tolerance; the f32-master /
     f32-optimizer-state invariant is asserted on the live TrainState.
  3. WALL-CLOCK tokens/sec f32 vs bf16 when a real TPU backend is
     attached (skipped on CPU — XLA's CPU bf16 path is emulation and
     the number would be noise).

    python tools/mp_bench.py             # full run -> BENCH_mp.json
    python tools/mp_bench.py --smoke     # CI gate: FAILS (exit 1) if
        simulated reduction < 1.3x on either model or if the bf16
        loss curve drifts past tolerance

ci.sh runs the smoke as step 1e.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _platform import select_platform  # noqa: E402

_plat = select_platform("MP_BENCH_PLATFORM")
if _plat == "cpu" and "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    # the simulated-reduction mesh is (4, 2): give the virtual CPU
    # platform 8 devices (must land before the first backend init)
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               + os.environ.get("XLA_FLAGS", ""))

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")

REDUCTION_GATE = 1.3
# bf16's ~8-bit mantissa wiggles each step; with f32 masters the walk
# stays on the f32 trajectory — 5% of the running loss magnitude holds
# with wide margin (observed ~0.3% on the transformer, docs/performance.md)
PARITY_TOL = 0.05


def _build_transformer(dtype_name):
    from flexflow_tpu import FFConfig
    from flexflow_tpu.models.transformer import build_transformer

    cfg = FFConfig(batch_size=64)
    cfg.compute_dtype = dtype_name
    cfg.search_cost_cache = False
    return build_transformer(cfg, batch_size=64, seq_len=512, hidden=512,
                             num_heads=8, num_layers=6, ff_dim=2048,
                             num_classes=10, layer_norm=True)


def _build_dlrm(dtype_name):
    from flexflow_tpu import FFConfig
    from flexflow_tpu.core.optimizers import SGDOptimizer
    from flexflow_tpu.models.dlrm import build_dlrm

    cfg = FFConfig(batch_size=8192)
    cfg.compute_dtype = dtype_name
    cfg.search_cost_cache = False
    ff = build_dlrm(cfg, batch_size=8192,
                    embedding_vocab_sizes=(100000,) * 26,
                    embedding_dim=64, bot_mlp=(512, 256, 64),
                    top_mlp=(1024, 1024, 512, 256, 1))
    # sparse-exact row updates — what compile() will run; op_cost reads
    # the optimizer's sparse_mode through the model
    ff.optimizer = SGDOptimizer(lr=0.01)
    return ff


def simulated_reductions():
    """{model: {f32_s, bf16_s, reduction}} on the TPU machine model
    over a d4 x m2 mesh — the strategy-search view of the bf16 lever."""
    from flexflow_tpu import make_mesh
    from flexflow_tpu.parallel.pconfig import Strategy
    from flexflow_tpu.search.cost_cache import machine_fingerprint
    from flexflow_tpu.search.simulator import Simulator

    out = {}
    fingerprints = {}
    for name, build in (("transformer", _build_transformer),
                        ("dlrm", _build_dlrm)):
        times = {}
        for dt in ("float32", "bfloat16"):
            ff = build(dt)
            mesh = make_mesh((4, 2), ("data", "model"))
            sim = Simulator(ff, mesh)
            times[dt] = sim.simulate(Strategy())
            fingerprints[dt] = machine_fingerprint(
                sim.mm, mesh, precision=sim._precision(),
                overlap=sim.overlap_sig())
        out[name] = {
            "f32_s": times["float32"],
            "bf16_s": times["bfloat16"],
            "reduction": times["float32"] / times["bfloat16"],
        }
    # the two fingerprints MUST differ — same machine, different
    # precision policy — or the cost cache would replay stale entries
    out["fingerprint_f32"] = fingerprints.get("float32")
    out["fingerprint_bf16"] = fingerprints.get("bfloat16")
    return out


def _train_curve(ff, batch, steps):
    import numpy as np
    losses = []
    for _ in range(steps):
        losses.append(float(ff.train_batch(batch)["loss"]))
    assert all(np.isfinite(losses)), losses
    return losses


def _assert_master_f32(ff, model_name):
    """The invariant the policy promises: master params and optimizer
    state stay f32 while the step computes in bf16."""
    import jax
    for leaf in jax.tree_util.tree_leaves(ff.state.params):
        assert str(leaf.dtype) == "float32", (
            f"{model_name}: master param dtype {leaf.dtype}")
    for leaf in jax.tree_util.tree_leaves(ff.state.opt_state):
        assert str(leaf.dtype) == "float32", (
            f"{model_name}: optimizer slot dtype {leaf.dtype}")


def parity(steps):
    """Train f32 vs bf16 on identical data; returns per-model curves
    and the max relative loss divergence."""
    import numpy as np
    from flexflow_tpu import FFConfig
    from flexflow_tpu.models.dlrm import build_dlrm
    from flexflow_tpu.models.transformer import build_transformer

    results = {}
    rng = np.random.RandomState(0)

    def small_transformer(dt):
        cfg = FFConfig(batch_size=8)
        cfg.compute_dtype = dt
        ff = build_transformer(cfg, batch_size=8, seq_len=64, hidden=64,
                               num_heads=4, num_layers=2, ff_dim=128,
                               num_classes=10, layer_norm=True)
        ff.compile(loss_type="sparse_categorical_crossentropy",
                   metrics=[])
        return ff

    tbatch = {"input": rng.randn(8, 64, 64).astype(np.float32),
              "label": rng.randint(0, 10, 8).astype(np.int32)}

    def small_dlrm(dt):
        cfg = FFConfig(batch_size=32)
        cfg.compute_dtype = dt
        ff = build_dlrm(cfg, batch_size=32,
                        embedding_vocab_sizes=(1000,) * 8)
        ff.compile(loss_type="binary_crossentropy", metrics=[])
        return ff

    dbatch = {"dense_features": rng.randn(32, 13).astype(np.float32),
              "label": rng.randint(0, 2, (32, 1)).astype(np.float32)}
    for i in range(8):
        dbatch[f"sparse_{i}"] = rng.randint(
            0, 1000, (32, 1)).astype(np.int32)

    for name, build, batch in (("transformer", small_transformer, tbatch),
                               ("dlrm", small_dlrm, dbatch)):
        f32 = build("float32")
        bf16 = build("bfloat16")
        cf = _train_curve(f32, batch, steps)
        cb = _train_curve(bf16, batch, steps)
        _assert_master_f32(bf16, name)
        max_rel = max(abs(a - b) / max(1.0, abs(a))
                      for a, b in zip(cf, cb))
        results[name] = {"loss_f32": cf, "loss_bf16": cb,
                         "max_rel_divergence": max_rel}
    return results


def wallclock(steps=20):
    """tokens/sec f32 vs bf16 on a REAL backend; None on CPU (bf16 is
    emulated there and the ratio means nothing)."""
    import jax
    if jax.default_backend() != "tpu":
        return None
    import numpy as np
    from flexflow_tpu import FFConfig
    from flexflow_tpu.models.transformer import build_transformer

    out = {}
    rng = np.random.RandomState(0)
    bs, seq = 32, 512
    batch_np = {"input": rng.randn(bs, seq, 512).astype(np.float32),
                "label": rng.randint(0, 10, bs).astype(np.int32)}
    for dt in ("float32", "bfloat16"):
        cfg = FFConfig(batch_size=bs)
        cfg.compute_dtype = dt
        ff = build_transformer(cfg, batch_size=bs, seq_len=seq,
                               hidden=512, num_heads=8, num_layers=6,
                               ff_dim=2048, num_classes=10,
                               layer_norm=True)
        ff.compile(loss_type="sparse_categorical_crossentropy",
                   metrics=[])
        batch = ff.executor.shard_batch(batch_np)
        float(ff.train_batch(batch)["loss"])  # compile
        t0 = time.perf_counter()
        m = None
        for _ in range(steps):
            m = ff.train_batch(batch)
        float(m["loss"])  # device->host sync delimits timing
        dt_s = (time.perf_counter() - t0) / steps
        out[dt] = {"step_s": dt_s, "tokens_per_sec": bs * seq / dt_s}
    out["speedup"] = (out["float32"]["step_s"]
                      / out["bfloat16"]["step_s"])
    return out


def main():
    import jax

    from flexflow_tpu.utils.cache_dirs import arm_compile_cache
    arm_compile_cache()
    smoke = "--smoke" in sys.argv
    out_path = None
    if "-o" in sys.argv:
        out_path = sys.argv[sys.argv.index("-o") + 1]

    sim = simulated_reductions()
    par = parity(steps=6 if smoke else 12)
    wall = None if smoke else wallclock()

    out = {
        "platform": jax.default_backend(),
        "simulated": sim,
        "parity": par,
        "parity_tol": PARITY_TOL,
        "reduction_gate": REDUCTION_GATE,
        "wallclock": wall,
    }
    for name in ("transformer", "dlrm"):
        s = sim[name]
        print(f"{name}: simulated f32 {s['f32_s']*1e6:.0f}us -> bf16 "
              f"{s['bf16_s']*1e6:.0f}us ({s['reduction']:.2f}x); "
              f"parity max rel divergence "
              f"{par[name]['max_rel_divergence']:.4f}")
    if wall:
        print(f"wall-clock: {wall['float32']['tokens_per_sec']:,.0f} -> "
              f"{wall['bfloat16']['tokens_per_sec']:,.0f} tok/s "
              f"({wall['speedup']:.2f}x)")

    if not smoke or out_path:
        path = out_path or os.path.join(ROOT, "BENCH_mp.json")
        with open(path, "w") as f:
            json.dump(out, f, indent=2)
            f.write("\n")
        print(f"wrote {os.path.normpath(path)}")

    ok = True
    for name in ("transformer", "dlrm"):
        r = sim[name]["reduction"]
        if r < REDUCTION_GATE:
            print(f"FAIL: {name} simulated bf16 reduction {r:.2f}x < "
                  f"{REDUCTION_GATE}x gate")
            ok = False
        d = par[name]["max_rel_divergence"]
        if d > PARITY_TOL:
            print(f"FAIL: {name} bf16 loss curve diverges from f32 "
                  f"({d:.4f} > {PARITY_TOL})")
            ok = False
    if sim["fingerprint_f32"] == sim["fingerprint_bf16"]:
        print("FAIL: cost-cache fingerprint does not separate "
              "precision policies")
        ok = False
    if not ok:
        return 1
    print(f"mp gates OK: reductions >= {REDUCTION_GATE}x, parity "
          f"within {PARITY_TOL}, fingerprints separate")
    return 0


if __name__ == "__main__":
    sys.exit(main())
