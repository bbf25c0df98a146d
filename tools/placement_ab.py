"""Placement A/B: measured step time vs simulator ranking for
device-explicit embedding placement (VERDICT r2 #5).

Reference analog: DLRM's strategy generator emits per-GPU table
placements (examples/cpp/DLRM/strategies/dlrm_strategy.cc:1-50) that
FFMapper::slice_task executes; the MCMC search justified them through
the simulator. Here the same loop closes on TPU: per-table device ids
lower to an executable slot layout (ops/embedding.py apply_placement),
and this script checks the simulator's placement win against measured
wall-clock on the live mesh.

Run on the 8-CPU virtual mesh:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python tools/placement_ab.py
or on real multi-chip TPU (no env needed). Prints one line per variant
plus a verdict comparing measured vs simulated orderings.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main(tables=8, vocab=None, dim=64, bs=None, steps=20):
    import jax
    import numpy as np

    on_cpu = jax.devices()[0].platform == "cpu"
    # CPU mesh: keep compiles in seconds — the ranking signal (gather
    # spread over devices vs serialized on one) survives small shapes
    vocab = vocab or (20_000 if on_cpu else 200_000)
    bs = bs or (256 if on_cpu else 1024)

    from flexflow_tpu import FFConfig, FFModel, SGDOptimizer, Strategy, \
        make_mesh
    from flexflow_tpu.parallel.pconfig import DEVICE_KEY, OpStrategy
    from flexflow_tpu.search.simulator import Simulator

    n = len(jax.devices())
    if n < 2:
        # single chip: placement has nothing to spread over — ask for
        # the 8-device virtual CPU mesh instead
        print(json.dumps({"skipped": "1 device; re-run with "
                          "XLA_FLAGS=--xla_force_host_platform_device_"
                          "count=8 JAX_PLATFORMS=cpu"}), flush=True)
        return 0
    mesh = make_mesh((n,), ("data",))

    def build(strategy):
        cfg = FFConfig()
        cfg.batch_size = bs
        ff = FFModel(cfg, mesh=mesh, strategy=strategy)
        ins = [ff.create_tensor((bs, 1), dtype=np.int32, name=f"s{i}")
               for i in range(tables)]
        embs = ff.distributed_embedding(ins, vocab, dim, name="tables")
        t = ff.concat(embs, axis=1)
        t = ff.dense(t, 64, activation="relu", name="top1")
        t = ff.dense(t, 4, name="top2")
        ff.softmax(t)
        ff.compile(optimizer=SGDOptimizer(lr=0.05),
                   loss_type="sparse_categorical_crossentropy",
                   metrics=[], mesh=mesh, strategy=strategy)
        return ff

    def strat(extra):
        s = Strategy(default=OpStrategy({"sample": "data"}))
        s.set("tables", OpStrategy(extra))
        return s

    variants = {
        "placed_round_robin": strat(
            {DEVICE_KEY: tuple(t % n for t in range(tables))}),
        "placed_one_device": strat({DEVICE_KEY: (0,) * tables}),
        "replicated": strat({}),
    }

    rng = np.random.RandomState(0)
    batch = {f"s{i}": rng.randint(0, vocab, (bs, 1)).astype(np.int32)
             for i in range(tables)}
    batch["label"] = rng.randint(0, 4, bs).astype(np.int32)

    results = {}
    for name, s in variants.items():
        ff = build(s)
        sim = Simulator(ff, mesh)
        predicted = sim.simulate(s)
        ff.train_batch(batch)  # compile + warmup
        t0 = time.perf_counter()
        for _ in range(steps):
            m = ff.train_batch(batch)
        float(m["loss"])  # device->host fetch closes the timed region
        dt = (time.perf_counter() - t0) / steps
        results[name] = {"measured_ms": round(dt * 1e3, 3),
                         "simulated_ms": round(predicted * 1e3, 6)}
        print(f"{name:22s} measured {dt * 1e3:9.3f} ms/step   "
              f"simulated {predicted * 1e3:9.3f} ms", flush=True)

    meas = sorted(results, key=lambda k: results[k]["measured_ms"])
    pred = sorted(results, key=lambda k: results[k]["simulated_ms"])
    verdict = {
        "measured_order": meas,
        "simulated_order": pred,
        "placement_win_measured":
            results["placed_round_robin"]["measured_ms"]
            < results["placed_one_device"]["measured_ms"],
        "placement_win_simulated":
            results["placed_round_robin"]["simulated_ms"]
            < results["placed_one_device"]["simulated_ms"],
        "results": results,
    }
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
