"""Benchmark driver — prints ONE JSON line on stdout, progress on stderr.

Default (`python bench.py`): the flagship Transformer-encoder training
step — samples/sec/chip and MFU vs the 0.30-MFU FlexFlow-V100 baseline
(BASELINE.md: the reference commits no numbers; its north star is "MFU
within 10% of FlexFlow's own V100-class results").

It measures in the process it was started in, on a TPU whose
`device_kind` it holds peaks for, or it exits non-zero without
printing a result: there is no subprocess ladder, no CPU rung and no
re-emission of an old capture. `--cpu-only` is the one explicit request
for a CPU run (tiny preset, the CPU platform forced); its line names
the platform, carries no utilization (there is no CPU peak to divide
by) and is never merged into bench_all.json.

`python bench.py --model M` benchmarks the other BASELINE.md configs
(alexnet, inception, dlrm, nmt_lstm); `--all` sweeps all five in this
one process and writes bench_all.json, still printing the flagship
line last. ROADMAP S0's chip benchmark replaces this file.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

MFU_BASELINE = 0.30
# bandwidth-bound models (DLRM) are scored against the HBM roofline with
# their OWN baseline constant so vs_baseline keeps consistent units
# ("fraction of the target utilization for this model's bound resource")
HBM_UTIL_BASELINE = 0.30
# per chip, keyed by a substring of jax's device_kind (Google Cloud TPU
# documentation). A device_kind that matches no key is an error.
PEAK_FLOPS = {
    # bf16 peak
    "v5 lite": 197e12,  # v5e device_kind reads "TPU v5 lite"
    "v5e": 197e12,
    "v5p": 459e12,
    "v4": 275e12,
    "v6 lite": 918e12,  # v6e device_kind reads "TPU v6 lite"
    "v6e": 918e12,
}
PEAK_HBM_BW = {
    # bytes/s
    "v5 lite": 819e9,
    "v5e": 819e9,
    "v5p": 2765e9,
    "v4": 1228e9,
    "v6 lite": 1640e9,
    "v6e": 1640e9,
}

MODELS = ["transformer", "alexnet", "inception", "dlrm", "nmt_lstm"]

# preset -> per-model shape overrides (batch, plus model-specific dims)
PRESETS = ("full", "small", "tiny")


def log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T0:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


T0 = time.perf_counter()


def peak_for(device_kind: str, table=PEAK_FLOPS) -> float:
    """The table's peak for a jax `device_kind`; an unknown kind raises
    (a utilization over an assumed peak is not a measurement)."""
    kind = device_kind.lower()
    for k, v in table.items():
        if k in kind:
            return v
    raise SystemExit(
        f"bench.py holds no peak for device_kind {device_kind!r} "
        f"(known: {sorted(table)}); add it with its source")


def step_bytes(ff, batch) -> float:
    """HBM bytes one training step moves — the numerator for a roofline
    utilization on bandwidth-bound models (DLRM), where MFU is
    structurally ~0 for any framework on any hardware. Source: XLA's
    OWN cost analysis of the compiled step ("bytes accessed" over the
    post-fusion HLO), not a hand model."""
    from flexflow_tpu.utils.profiling import hlo_cost
    b = float(hlo_cost(ff, batch).get("bytes accessed", 0.0))
    if b <= 0:
        raise SystemExit("XLA cost analysis reported no bytes accessed "
                         "for the compiled step")
    return b


def positive_int_env(name: str, default: int) -> int:
    """Sweep-knob env var -> positive int, failing loudly on junk."""
    v = os.environ.get(name)
    if not v:
        return default
    try:
        n = int(v)
    except ValueError:
        raise SystemExit(f"{name}={v!r} is not an integer")
    if n <= 0:
        raise SystemExit(f"{name} must be positive, got {n}")
    return n


def build(model: str, preset: str):
    """Returns (ff, batch_data), compiled and ready to train."""
    import jax.numpy as jnp
    from flexflow_tpu import FFConfig, SGDOptimizer
    from flexflow_tpu import models as zoo

    rng = np.random.RandomState(0)
    cfg = FFConfig()
    # conv compute-layout A/B knob (tools/inception_audit.py sets it)
    layout = os.environ.get("BENCH_CONV_LAYOUT")
    if layout:
        cfg.conv_layout = layout

    def _b(default):
        # BENCH_BATCH: sweep knob for per-chip batch (MFU is
        # batch-sensitive on conv models)
        return positive_int_env("BENCH_BATCH", default)

    if model == "transformer":
        batch, seq, hidden, layers, ffd = {
            "full": (32, 512, 512, 6, 2048),
            "small": (16, 256, 512, 4, 2048),
            "tiny": (8, 64, 128, 2, 256),
        }[preset]
        batch = _b(batch)
        cfg.batch_size = batch
        ff = zoo.build_transformer(cfg, batch_size=batch, seq_len=seq,
                                   hidden=hidden, num_heads=8,
                                   num_layers=layers, ff_dim=ffd,
                                   num_classes=10, dtype=jnp.bfloat16)
        data = {"input": jnp.asarray(
            rng.randn(batch, seq, hidden), jnp.bfloat16),
            "label": jnp.asarray(rng.randint(0, 10, (batch,)), jnp.int32)}
    elif model == "alexnet":
        batch = _b({"full": 256, "small": 128, "tiny": 16}[preset])
        cfg.batch_size = batch
        # bf16 activations (weights f32): MXU-native mixed precision,
        # same mode the transformer config benches in
        ff = zoo.build_alexnet(cfg, batch_size=batch, dtype=jnp.bfloat16)
        data = {"input": jnp.asarray(
            rng.randn(batch, 3, 32, 32), jnp.bfloat16),
            "label": jnp.asarray(rng.randint(0, 10, (batch,)), jnp.int32)}
    elif model == "inception":
        batch = _b({"full": 32, "small": 16, "tiny": 4}[preset])
        size = {"full": 299, "small": 299, "tiny": 75}[preset]
        cfg.batch_size = batch
        ff = zoo.build_inception_v3(cfg, batch_size=batch, image_size=size,
                                    dtype=jnp.bfloat16)
        data = {"input": jnp.asarray(
            rng.randn(batch, 3, size, size), jnp.bfloat16),
            "label": jnp.asarray(rng.randint(0, 10, (batch,)), jnp.int32)}
    elif model == "dlrm":
        # Criteo-like shape (reference run scripts: 26 sparse features,
        # ~1M vocab, bag 1, examples/cpp/DLRM/run_summit.sh); large batch
        # because DLRM is bandwidth/latency-bound, not FLOPs-bound — at
        # batch 1024 even a perfect step is <0.1ms of HBM traffic and
        # every framework measures overhead, not hardware
        batch = _b({"full": 8192, "small": 2048, "tiny": 64}[preset])
        vocab = {"full": 1000000, "small": 100000, "tiny": 1000}[preset]
        ntab = {"full": 26, "small": 26, "tiny": 8}[preset]
        cfg.batch_size = batch
        vocabs = (vocab,) * ntab
        ff = zoo.build_dlrm(cfg, batch_size=batch,
                            embedding_vocab_sizes=vocabs)
        data = {"dense_features": jnp.asarray(
            rng.randn(batch, 13), jnp.float32),
            "label": jnp.asarray(
                rng.rand(batch, 1) > 0.5, jnp.float32)}
        for i in range(len(vocabs)):
            data[f"sparse_{i}"] = jnp.asarray(
                rng.randint(0, vocabs[i], (batch, 1)), jnp.int32)
    elif model == "nmt_lstm":
        # batch 256: the recurrent h@Wh GEMM's M dim IS the batch — at 64
        # it fills half the MXU sublanes; 256 fills the pipeline (the
        # reference nmt trains large global batches across GPUs too)
        batch, seq = {"full": (256, 40), "small": (64, 40),
                      "tiny": (8, 10)}[preset]
        batch = _b(batch)
        cfg.batch_size = batch
        ff = zoo.build_nmt_lstm(cfg, batch_size=batch, seq_len=seq,
                                dtype=jnp.bfloat16)
        data = {"input": jnp.asarray(
            rng.randint(0, 32000, (batch, seq)), jnp.int32),
            "label": jnp.asarray(rng.randint(0, 32000, (batch,)),
                                 jnp.int32)}
    else:
        raise SystemExit(f"unknown --model {model}")
    loss = ("mean_squared_error" if model == "dlrm"
            else "sparse_categorical_crossentropy")
    ff.compile(optimizer=SGDOptimizer(lr=0.01), loss_type=loss, metrics=[])
    return ff, data


def measure(model: str, preset: str, steps: int, on_tpu: bool) -> dict:
    """Measure one config in THIS process; returns the result dict."""
    import jax
    dev = jax.devices()[0]
    log(f"model={model} preset={preset} on {dev.device_kind} "
        f"({dev.platform}) x{len(jax.devices())}")
    ff, batch_data = build(model, preset)
    log("model built + compiled graph-side; warming up (jit compile)...")
    batch = next(iter(batch_data.values())).shape[0]
    fwd_flops = sum(op.flops() for op in ff.ops)
    # Standard MFU accounting: step = fwd + 2x-fwd backward. (The search
    # cost model prices attention backward at 4x because flash RECOMPUTES
    # probabilities — recompute is overhead, not useful work, so it is
    # deliberately excluded here; counting it would inflate MFU.)
    step_flops = 3.0 * fwd_flops

    # warmup (includes compile). Every timing region below is closed by
    # a device->host fetch of the loss, which cannot return before the
    # device has produced it.
    t_c = time.perf_counter()
    nbytes = None
    if model == "dlrm" and on_tpu:
        # the roofline byte source compiles the single-step program AOT;
        # doing it INSTEAD of the single-step warmup keeps total
        # compiles at two (single + scanned multi), same as every other
        # model — the multi-step warmup below still warms the device
        nbytes = step_bytes(ff, batch_data)
        log(f"single-step cost probe in {time.perf_counter() - t_c:.1f}s")
    else:
        m = ff.train_batch(batch_data)
        float(m["loss"])
        log(f"first step (compile) done in "
            f"{time.perf_counter() - t_c:.1f}s")
    # measure through the scanned multi-step dispatch (train_batches =
    # the Legion trace-replay analog): one host round trip per DISPATCH
    # of `per_dispatch` steps, so dispatch latency is amortized the same
    # way begin/end_trace amortizes Legion dependence analysis in the
    # reference hot loop (alexnet.cc:106-111)
    per_dispatch = min(10, steps)
    # two candidate groupings: the K-step program, then 1 step/dispatch.
    # The K-step program double-buffers the carried params, so at param
    # scales near HBM capacity (DLRM 26x1M tables) it can OOM where the
    # single-step program (true in-place donation) fits.
    for pd_try in dict.fromkeys((per_dispatch, 1)):
        try:
            per_dispatch = pd_try
            group = ff.stage_batches([batch_data] * per_dispatch)
            t_c = time.perf_counter()
            m = ff.train_batches(group)
            float(np.sum(np.asarray(m["loss"], dtype=np.float64)))
            log(f"{per_dispatch}-step compile done in "
                f"{time.perf_counter() - t_c:.1f}s")
            break
        except Exception as exc:  # noqa: BLE001
            msg = str(exc).lower()
            # XLA/TPU allocators phrase OOM three ways: "ran out of
            # memory", "out of memory while trying to allocate", and
            # bare RESOURCE_EXHAUSTED status strings
            oom = ("out of memory" in msg or "resource_exhausted" in msg
                   or "resource exhausted" in msg)
            if pd_try == 1 or not oom:
                raise
            log(f"multi-step scan OOM'd "
                f"({str(exc).splitlines()[0][:120]}); "
                f"retrying with per_dispatch=1")
            # an EXECUTION-time OOM has already consumed the donated
            # state buffers ("Array has been deleted" on reuse) —
            # rebuild fresh; build() is deterministic (seeded)
            ff, batch_data = build(model, preset)
    n_disp = max(1, steps // per_dispatch)
    log(f"warmup done; timing {n_disp} dispatches x {per_dispatch} steps...")

    # best-of-3 timed passes: the minimum over repeated async passes is
    # the robust estimate of sustained device throughput
    def timed_pass():
        t0 = time.perf_counter()
        for _ in range(n_disp):
            m = ff.train_batches(group)
        float(np.sum(np.asarray(m["loss"], dtype=np.float64)))  # drain
        return (time.perf_counter() - t0) / (n_disp * per_dispatch)

    dts = [timed_pass() for _ in range(3)]
    dt = min(dts)
    log(f"steps done: {dt * 1e3:.2f} ms/step "
        f"(best of {[round(d * 1e3, 2) for d in dts]})")

    extra = {"ms_per_step": round(dt * 1e3, 3), "preset": preset,
             "platform": dev.platform, "device_kind": dev.device_kind,
             "device_count": len(jax.devices()),
             "batch": batch, "steps": steps,
             "per_dispatch": per_dispatch,
             "captured": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                       time.gmtime())}
    metric = (f"{model}_train_samples_per_sec_per_chip"
              if model != "transformer"
              else "transformer_encoder_train_samples_per_sec_per_chip")
    result = {"metric": metric, "value": round(batch / dt, 2),
              "unit": "samples/s", "vs_baseline": None, "extra": extra}
    if not on_tpu:
        # --cpu-only: a host number under its own name, no utilization
        result["metric"] = metric + "_cpu_only"
        return result
    mfu = step_flops / dt / peak_for(dev.device_kind)
    extra["mfu"] = round(mfu, 4)
    util, util_baseline = mfu, MFU_BASELINE
    extra["util_basis"] = "mfu"
    if model == "dlrm":
        # bandwidth-bound: score distance to the HBM roofline, not the
        # MXU one (MFU stays in extras; DLRM's useful work per byte is
        # tiny by construction — embedding rows dominate). vs_baseline
        # stays unit-consistent: it divides the roofline utilization by
        # a BANDWIDTH baseline constant (HBM_UTIL_BASELINE), and the
        # basis is declared in the JSON (util_basis).
        hbm_util = nbytes / dt / peak_for(dev.device_kind, PEAK_HBM_BW)
        extra["hbm_util"] = round(hbm_util, 4)
        if hbm_util >= mfu:
            util, util_baseline = hbm_util, HBM_UTIL_BASELINE
            extra["util_basis"] = "hbm_roofline_xla"
    result["vs_baseline"] = round(util / util_baseline, 4)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="transformer", choices=MODELS)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--preset", default="full", choices=PRESETS)
    ap.add_argument("--all", action="store_true",
                    help="sweep all five BASELINE.md configs; write "
                         "bench_all.json; print the flagship line last")
    ap.add_argument("--cpu-only", action="store_true",
                    help="measure the tiny preset on the CPU platform "
                         "deliberately (no utilization, never merged "
                         "into bench_all.json)")
    args = ap.parse_args()

    if args.cpu_only:
        # before jax is imported: the variable alone decides
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    from flexflow_tpu.utils.cache_dirs import arm_compile_cache
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.cpu_only:
        log(f"no TPU: jax's backend is {dev.platform!r} "
            f"({dev.device_kind}). bench.py measures on a TPU or not at "
            f"all; --cpu-only asks for a CPU run explicitly")
        return 2
    if on_tpu:
        peak_for(dev.device_kind)  # unknown kind: fail before measuring
    cache_dir, was_empty = arm_compile_cache()
    log(f"compile cache {cache_dir} "
        f"({'empty' if was_empty else 'has entries'})")
    preset = args.preset if on_tpu else "tiny"
    steps = args.steps if on_tpu else max(5, args.steps // 4)

    if not args.all:
        print(json.dumps(measure(args.model, preset, steps, on_tpu)),
              flush=True)
        return 0
    # flagship first: whatever ran before a failure is what is kept
    order = ["transformer"] + [m for m in MODELS if m != "transformer"]
    results = {m: measure(m, preset, steps, on_tpu) for m in order}
    if on_tpu:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "bench_all.json")
        with open(path, "w") as f:
            json.dump(results, f, indent=2)
    print(json.dumps(results["transformer"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
