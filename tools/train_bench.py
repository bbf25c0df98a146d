"""Training-runtime benchmark: the async/overlap runtime vs the
synchronous dispatch path.

Two arms over identical data, identical seeds, identical step count:

  * sync    — the legacy loop: one dispatch per step, host blocks on
              every step's metrics (depth-1 window), monolithic
              end-of-backward grad sync (grad_bucket_mb=0), main-thread
              batch staging.
  * overlap — the async runtime this repo now ships: K-step grouped
              dispatch (train_batches — ONE host round trip and ONE
              stacked staging transfer per K steps), a depth-2 dispatch
              window (group g's metrics retrieved while group g+1 is in
              flight), and bucketed backward-overlapped grad sync
              (grad_bucket_mb).

The loss trajectories must be BIT-identical between the arms (the
window changes WHEN results are fetched, the scan body is the same
step math, and the bucket sync points are custom_vjp identities), and
nothing may compile after warmup — both asserted under --smoke (CI
gate, tools/ci.sh step 1h) along with step-time reduction >= 1.10x on
the primary (dlrm) workload.

Workloads (both gated >= --gate under --smoke):
  * dlrm        — a 26-table DLRM step is dispatch/staging-bound (28
                  host arrays per step, a short memory-bound device
                  step): the regime where per-step dispatch overhead
                  dominates and grouping/pipelining pays most.
  * transformer — the flagship model; its CPU win comes from the
                  grouped dispatch amortizing the runtime's per-program
                  execution overhead over K scanned steps. On TPU the
                  transformer's additional async-runtime win is
                  comm-overlap, which the `sim` record prices (bucketed
                  overlap vs serialized sync on the TPU machine model —
                  the same pricing the MCMC search now uses) and
                  bench.py measures end to end (vs_baseline).

Writes/merges records into BENCH_train.json (merge-by-metric like
serve_bench, so partial runs never clobber other records):

    python tools/train_bench.py --smoke      # the CI gate
    python tools/train_bench.py              # full sizes
"""

import argparse
import json
import os
import sys
import time

# this bench measures the host runtime — pin CPU before jax loads
# unless the caller asks for the ambient backend
if "--ambient-backend" not in sys.argv:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # virtual devices for the `sim` record's d8 pricing mesh (the
    # timed arms run single-device regardless — no mesh is passed)
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               + os.environ.get("XLA_FLAGS", ""))

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np  # noqa: E402


def log(msg):
    print(f"[train_bench] {msg}", file=sys.stderr, flush=True)


def _build(model, args, overlap):
    import jax
    from flexflow_tpu import FFConfig, SGDOptimizer
    from flexflow_tpu.models.dlrm import build_dlrm
    from flexflow_tpu.models.transformer import build_transformer

    cfg = FFConfig(batch_size=args.batch)
    cfg.train_dispatch_depth = 2 if overlap else 1
    cfg.grad_bucket_mb = args.bucket_mb if overlap else 0.0
    rng = np.random.RandomState(0)
    n = args.batch * max(4, args.group)
    if model == "dlrm":
        vocabs = (args.vocab,) * args.tables
        ff = build_dlrm(cfg, batch_size=args.batch,
                        embedding_vocab_sizes=vocabs,
                        embedding_dim=16, bot_mlp=(64, 32, 16),
                        top_mlp=(64, 1))
        ff.compile(optimizer=SGDOptimizer(lr=0.01),
                   loss_type="mean_squared_error", metrics=[])
        x = {"dense_features": rng.randn(n, 13)}
        for i in range(args.tables):
            x[f"sparse_{i}"] = rng.randint(
                0, args.vocab, (n, 1)).astype(np.int64)
        y = (rng.rand(n, 1) > 0.5).astype(np.float64)
    else:
        ff = build_transformer(
            cfg, batch_size=args.batch, seq_len=args.seq,
            hidden=args.hidden, num_heads=4, num_layers=args.layers,
            ff_dim=args.hidden * 2, num_classes=10)
        ff.compile(optimizer=SGDOptimizer(lr=0.01),
                   loss_type="sparse_categorical_crossentropy",
                   metrics=[])
        x = {"input": rng.randn(n, args.seq, args.hidden)}
        y = rng.randint(0, 10, (n,)).astype(np.int64)
    del jax  # imported for backend init side effect ordering
    return ff, x, y


def run_arm(model, args, overlap):
    """-> (sec/step best-of-repeats, losses float32 array, stats)."""
    import jax
    from flexflow_tpu.core.overlap import DispatchWindow
    from flexflow_tpu.core.programs import CompileEvents

    ff, x, y = _build(model, args, overlap)
    names = list(x)
    bs = args.batch
    nbatch = len(y) // bs
    K = args.group if overlap else 1

    def mk(s):
        sel = slice((s % nbatch) * bs, ((s % nbatch) + 1) * bs)
        b = {k: x[k][sel] for k in names}
        b["label"] = y[sel]
        return b

    depth = ff.config.train_dispatch_depth
    win = DispatchWindow(depth)
    losses = []
    gaps = []
    last_end = [None]

    def dispatch(step0):
        t = time.perf_counter()
        if last_end[0] is not None:
            gaps.append(t - last_end[0])
        if K > 1:
            m = ff.train_batches([mk(step0 + i) for i in range(K)])
        else:
            m = ff.train_batch(mk(step0))
        last_end[0] = time.perf_counter()
        win.push(m)

    def drain():
        for m in win.drain():
            arr = np.asarray(m["loss"], dtype=np.float32).reshape(-1)
            losses.extend(arr.tolist())

    # warmup: compile both in-flight program shapes
    warm = max(K, args.warmup - args.warmup % K or K)
    for s in range(0, warm, K):
        dispatch(s)
    drain()
    installed = CompileEvents.install()
    compiles0 = CompileEvents.count
    best = float("inf")
    step = warm
    for _ in range(args.repeat):
        t0 = time.perf_counter()
        for _g in range(args.steps // K):
            dispatch(step)
            step += K
        drain()
        best = min(best, (time.perf_counter() - t0) / args.steps)
    compiles = (CompileEvents.count - compiles0) if installed else None
    sg = sorted(gaps)
    stats = {
        "depth": depth,
        "group": K,
        "grad_bucket_mb": ff.config.grad_bucket_mb,
        "grad_buckets": ff.executor.grad_bucket_info()["count"],
        "dispatch_gap_ms_mean": round(1e3 * sum(sg) / len(sg), 4)
        if sg else 0.0,
        "dispatch_gap_ms_p50": round(1e3 * sg[len(sg) // 2], 4)
        if sg else 0.0,
        "dispatch_gap_ms_max": round(1e3 * sg[-1], 4) if sg else 0.0,
        "fetch_wait_ms_total": round(1e3 * sum(win.fetch_waits_s), 3),
        "compiles_after_warmup": compiles,
        "platform": jax.default_backend(),
    }
    return best, np.asarray(losses, dtype=np.float32), stats


def sim_overlap_record(args):
    """Simulated transformer step on the TPU machine model, bucketed
    overlap vs serialized monolithic sync — the pricing the MCMC search
    now rewards (the executor's measured win on real TPUs rides
    bench.py's vs_baseline)."""
    from flexflow_tpu import FFConfig, make_mesh
    from flexflow_tpu.models.transformer import build_transformer
    from flexflow_tpu.parallel.mesh import MachineSpec
    from flexflow_tpu.parallel.pconfig import Strategy
    from flexflow_tpu.search.cost_cache import machine_fingerprint
    from flexflow_tpu.search.machine_model import default_machine_model
    from flexflow_tpu.search.simulator import Simulator

    mesh = make_mesh((8,), ("data",))
    mm = default_machine_model(mesh, spec=MachineSpec.v5e())

    def priced(overlap_on):
        cfg = FFConfig(batch_size=64)
        cfg.search_overlap_backward_sync = overlap_on
        cfg.grad_bucket_mb = args.bucket_mb if overlap_on else 0.0
        ff = build_transformer(cfg, batch_size=64, seq_len=512,
                               hidden=512, num_heads=8, num_layers=6,
                               ff_dim=2048, num_classes=10)
        sim = Simulator(ff, mesh, mm)
        return sim.simulate(Strategy()), sim

    t_sync, _ = priced(False)
    t_ovl, sim = priced(True)
    return {
        "metric": "train_sim_overlap_step_reduction",
        "value": round(t_sync / t_ovl, 4),
        "unit": "x",
        "extra": {
            "sync_s": t_sync, "overlap_s": t_ovl,
            "machine": "v5e d8", "model": "transformer 6L h512 s512",
            "grad_bucket_mb": args.bucket_mb,
            "fingerprint": machine_fingerprint(
                sim.mm, mesh, precision=sim._precision(),
                overlap=sim.overlap_sig()),
        },
    }


def telemetry_record(args):
    """A small telemetry-on fit() over the bench transformer: exports
    the train metrics snapshot (dispatch gaps, fetch waits, window
    stats) and the train half of the simulator-drift calibration
    (measured wall/step vs the overlap-exact graph's prediction) into
    the BENCH artifact — the perf trajectory carries the numbers the
    string report renders (docs/observability.md)."""
    from flexflow_tpu import FFConfig, SGDOptimizer
    from flexflow_tpu.models.transformer import build_transformer

    cfg = FFConfig(batch_size=args.batch)
    cfg.telemetry = True
    ff = build_transformer(
        cfg, batch_size=args.batch, seq_len=args.seq,
        hidden=args.hidden, num_heads=4, num_layers=args.layers,
        ff_dim=args.hidden * 2, num_classes=10)
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type="sparse_categorical_crossentropy", metrics=[])
    rng = np.random.RandomState(0)
    n = args.batch * 4
    x = {"input": rng.randn(n, args.seq, args.hidden)}
    y = rng.randint(0, 10, (n,)).astype(np.int64)
    ff.fit(x, y, epochs=2, verbose=False)
    tel = ff.telemetry
    snap = tel.metrics_snapshot()
    drift = snap["drift"].get("train", {})
    st = ff.last_train_stats
    return {
        "metric": "train_telemetry_profile",
        "value": st["dispatches"],
        "unit": "dispatches",
        "extra": {
            "dispatch_gap_ms_mean": round(
                st["dispatch_gap_s_mean"] * 1e3, 4),
            "dispatch_gap_ms_p50": round(
                st["dispatch_gap_s_p50"] * 1e3, 4),
            "dispatch_gap_ms_max": round(
                st["dispatch_gap_s_max"] * 1e3, 4),
            "fetch_wait_ms_total": round(
                st["fetch_wait_s_total"] * 1e3, 3),
            "max_in_flight": st["max_in_flight"],
            "events_buffered": snap["events_buffered"],
            "drift_ratio_by_regime": {
                reg: round(d["ratio"], 2) for reg, d in drift.items()},
            "drift_predicted_ms_per_step": {
                reg: round(d["predicted_ms_per_step"], 4)
                for reg, d in drift.items()},
            "drift_measured_ms_per_step": {
                reg: round(d["measured_ms_per_step"], 4)
                for reg, d in drift.items()},
        },
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: small sizes; assert >= --gate "
                         "step-time reduction per workload, "
                         "bit-identical losses, zero recompiles after "
                         "warmup")
    ap.add_argument("--workload", choices=("all", "dlrm", "transformer",
                                           "sim", "telemetry"),
                    default="all")
    ap.add_argument("--steps", type=int, default=64)
    ap.add_argument("--repeat", type=int, default=3)
    ap.add_argument("--warmup", type=int, default=8)
    ap.add_argument("--group", type=int, default=8,
                    help="steps per grouped dispatch in the overlap arm")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=2000)
    ap.add_argument("--tables", type=int, default=26)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--bucket-mb", type=float, default=4.0)
    ap.add_argument("--gate", type=float, default=1.10)
    ap.add_argument("--ambient-backend", action="store_true",
                    help="don't pin JAX_PLATFORMS=cpu (measure on the "
                         "ambient TPU backend)")
    ap.add_argument("-o", "--out", default="BENCH_train.json")
    args = ap.parse_args()
    if args.smoke:
        args.steps = min(args.steps, 48)
        args.repeat = min(args.repeat, 3)
    args.steps -= args.steps % args.group  # one program shape per arm
    from flexflow_tpu.utils.cache_dirs import arm_compile_cache
    arm_compile_cache()

    os.environ.setdefault(
        "FLEXFLOW_TPU_CACHE",
        os.path.join("/tmp", "flexflow_tpu_train_bench_cache"))

    records = []
    gates = []
    workloads = (["dlrm", "transformer"] if args.workload == "all"
                 else [args.workload]
                 if args.workload in ("dlrm", "transformer") else [])
    for model in workloads:
        log(f"{model}: sync arm ({args.steps} steps x{args.repeat})...")
        t_sync, l_sync, s_sync = run_arm(model, args, overlap=False)
        log(f"{model}: overlap arm...")
        t_ovl, l_ovl, s_ovl = run_arm(model, args, overlap=True)
        red = t_sync / t_ovl if t_ovl > 0 else 0.0
        exact = (l_sync.shape == l_ovl.shape
                 and np.array_equal(l_sync, l_ovl))
        rec = {
            "metric": f"train_overlap_step_reduction_{model}",
            "value": round(red, 4),
            "unit": "x",
            "extra": {
                "sync_ms_per_step": round(t_sync * 1e3, 3),
                "overlap_ms_per_step": round(t_ovl * 1e3, 3),
                "samples_per_sec_sync": round(args.batch / t_sync, 1),
                "samples_per_sec_overlap": round(args.batch / t_ovl, 1),
                "steps": args.steps, "batch": args.batch,
                "loss_trajectory_bit_identical": bool(exact),
                "sync": s_sync, "overlap": s_ovl,
                "captured": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                          time.gmtime()),
            },
        }
        records.append(rec)
        log(f"{model}: sync {t_sync*1e3:.2f} ms/step, overlap "
            f"{t_ovl*1e3:.2f} ms/step -> {red:.2f}x, exact={exact}, "
            f"compiles after warmup: sync="
            f"{s_sync['compiles_after_warmup']} "
            f"overlap={s_ovl['compiles_after_warmup']}")
        if args.smoke:
            assert exact, (
                f"{model}: overlap-arm loss trajectory diverged from "
                f"the synchronous path (must be bit-identical)")
            for arm_name, st in (("sync", s_sync), ("overlap", s_ovl)):
                c = st["compiles_after_warmup"]
                assert c in (0, None), (
                    f"{model}/{arm_name}: {c} compiles after warmup "
                    f"(zero-recompile gate)")
            assert red >= args.gate, (
                f"{model} step-time reduction {red:.3f}x < gate "
                f"{args.gate}x")
            gates.append(f"{model}_reduction={red:.2f}x>={args.gate}x")
            gates.append(f"{model}_exact+zero_recompiles")

    if args.workload in ("all", "sim"):
        log("simulated overlap pricing (TPU machine model)...")
        rec = sim_overlap_record(args)
        records.append(rec)
        log(f"sim: {rec['value']}x step reduction "
            f"(sync {rec['extra']['sync_s']*1e3:.3f} ms -> overlap "
            f"{rec['extra']['overlap_s']*1e3:.3f} ms)")
        if args.smoke:
            assert rec["value"] >= 1.0, (
                f"simulator prices overlapped sync SLOWER than "
                f"serialized ({rec['value']}x)")
            gates.append(f"sim_reduction={rec['value']}x>=1.0x")

    if args.workload in ("all", "telemetry"):
        log("telemetry profile (telemetry-on fit + drift)...")
        rec = telemetry_record(args)
        records.append(rec)
        log(f"telemetry: {rec['value']} dispatches, drift regimes: "
            f"{list(rec['extra']['drift_ratio_by_regime'])}")
        if args.smoke:
            assert rec["extra"]["events_buffered"] > 0, (
                "telemetry-on fit recorded no events")
            assert rec["extra"]["drift_ratio_by_regime"], (
                "telemetry-on fit recorded no train drift regimes")
            gates.append("telemetry_profile+drift recorded")

    # merge-by-metric (serve_bench convention): partial --workload runs
    # never clobber the other records
    merged = {}
    try:
        with open(args.out) as f:
            for line in f.read().splitlines():
                if line.strip():
                    r = json.loads(line)
                    merged[r["metric"]] = r
    except (OSError, json.JSONDecodeError):
        pass
    for r in records:
        merged[r["metric"]] = r
    with open(args.out, "w") as f:
        f.write("\n".join(json.dumps(r) for r in merged.values()) + "\n")
    print("\n".join(json.dumps(r) for r in records))
    if args.smoke:
        log("GATES PASSED: " + "; ".join(gates))
    return 0


if __name__ == "__main__":
    sys.exit(main())
