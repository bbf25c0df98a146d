"""Pipeline bubble: simulated-vs-analytic agreement + CPU wall-clock.

Validates VERDICT r3 #7's "simulated-vs-measured bubble agreement" with
the two signals this host can actually produce:

1. SIMULATOR vs ANALYTIC: the event-loop simulator's makespan for a
   staged strategy (search/simulator.py _simulate_staged — per-stage
   resources, per-cut hops) against the closed-form GPipe tick model
   time ∝ (M + S - 1)/M (graph_pipeline.simulate_step_scaling). Agrees
   in the compute-dominated regime; diverges where per-hop latency
   binds (more microbatches = more, smaller hops) — which is the
   simulator being MORE faithful than the closed form, not less.

2. WALL-CLOCK on the forced 8-device CPU platform. CAVEAT: this box has
   ONE physical core (nproc=1), so the 8 "devices" serialize and
   wall-clock measures TOTAL work + dispatch overhead, not the critical
   path — the bubble the schedule hides is invisible here. Recorded as
   a liveness/overhead signal only; on-chip wall-clock agreement needs
   real multi-chip hardware.

Writes evidence/pipeline_bubble_cpu8.json. Run:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python tools/pipeline_bubble_ab.py
"""

import json
import os
import sys
import time

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer, make_mesh  # noqa: E402
from flexflow_tpu.parallel.graph_pipeline import (  # noqa: E402
    simulate_step_scaling,
)
from flexflow_tpu.search.mcmc import staged_strategies  # noqa: E402
from flexflow_tpu.search.simulator import Simulator  # noqa: E402

BS = 256
FEAT = 2048
STAGES = 2


def build_model(m, schedule="gpipe", feat=FEAT, bs=BS, compile_=False,
                mesh=None):
    cfg = FFConfig(batch_size=bs)
    cfg.enable_pipeline_parallel = True
    cfg.pipeline_stages = STAGES if compile_ else 0
    cfg.pipeline_microbatches = m
    cfg.pipeline_schedule = schedule
    ff = FFModel(cfg, mesh=mesh)
    x = ff.create_tensor((bs, feat), name="input")
    t = x
    for i in range(8):
        t = ff.dense(t, feat, activation="relu", name=f"fc{i}")
    t = ff.dense(t, 10, name="head")
    ff.softmax(t)
    if compile_:
        ff.compile(optimizer=SGDOptimizer(lr=0.01),
                   loss_type="sparse_categorical_crossentropy",
                   metrics=[], mesh=mesh)
    return ff


def sim_vs_analytic():
    mesh = make_mesh((STAGES,), ("pipe",))
    rows = []
    base = None
    for m in (1, 2, 4, 8, 16):
        ff = build_model(m)
        staged = staged_strategies(ff, mesh, ff.config)[0]
        t = Simulator(ff, mesh).simulate(staged)
        if base is None:
            base = t
        rows.append({
            "microbatches": m,
            "sim_us": t * 1e6,
            "sim_speedup_vs_m1": base / t,
            "analytic_speedup_vs_m1": simulate_step_scaling(STAGES, 1, m),
        })
    return rows


def wall_clock(schedule):
    mesh = make_mesh((STAGES,), ("pipe",))
    rows = []
    rng = np.random.RandomState(0)
    bs = 64
    b = {"input": rng.randn(bs, 256).astype(np.float32),
         "label": rng.randint(0, 10, bs).astype(np.int32)}
    for m in (1, 4):
        ff = build_model(m, schedule=schedule, feat=256, bs=bs,
                         compile_=True, mesh=mesh)
        float(ff.train_batch(b)["loss"])  # compile + warmup
        t0 = time.perf_counter()
        for _ in range(10):
            r = ff.train_batch(b)
        float(r["loss"])
        rows.append({"microbatches": m,
                     "ms_per_step": (time.perf_counter() - t0) * 100})
    return rows


def interleaved_bubbles():
    """Schedule-level bubble fractions: plain 1F1B (v=1) vs the
    interleaved wave schedule at v in {2, 4} (round 4's
    --pipeline-virtual-stages), and the forward-only schedule that
    eval/predict runs (`pipeline_logits_interleaved`)."""
    from flexflow_tpu.parallel.graph_pipeline import (
        interleaved_forward_schedule, interleaved_schedule,
        schedule_bubble)
    rows = []
    for D, M in [(2, 8), (4, 8), (4, 16), (8, 32)]:
        row = {"devices": D, "microbatches": M}
        for v in (1, 2, 4):
            kind, _m, _s, depth = interleaved_schedule(D, v, M)
            row[f"bubble_v{v}"] = round(schedule_bubble(kind), 4)
            row[f"depth_v{v}"] = depth
            fkind, _fm, _fs, fdepth = interleaved_forward_schedule(
                D, v, M)
            row[f"fwd_bubble_v{v}"] = round(schedule_bubble(fkind), 4)
            row[f"fwd_depth_v{v}"] = fdepth
        rows.append(row)
    return rows


def main():
    out = {"stages": STAGES, "nproc": os.cpu_count(),
           "interleaved_schedule_bubbles": interleaved_bubbles(),
           "sim_vs_analytic": sim_vs_analytic(),
           "wall_clock_caveat": (
               "1 physical core: devices serialize; wall-clock = total "
               "work, bubble invisible (see module docstring)"),
           "wall_clock": {s: wall_clock(s) for s in ("gpipe", "1f1b")}}
    print("sim vs analytic (speedup over M=1 at fixed batch):")
    for r in out["sim_vs_analytic"]:
        print(f"  M={r['microbatches']:>2}: sim x{r['sim_speedup_vs_m1']:.3f}"
              f"  analytic x{r['analytic_speedup_vs_m1']:.3f}")
    path = os.path.join(os.path.dirname(__file__), "..", "evidence",
                        "pipeline_bubble_cpu8.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {os.path.normpath(path)}")


if __name__ == "__main__":
    main()
