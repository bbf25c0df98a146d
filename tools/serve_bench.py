#!/usr/bin/env python
"""Offline serving throughput microbench (flexflow_tpu.serve).

Three workloads through ServeEngine under continuous batching:

  * random   — synthetic ragged prompts; reports aggregate tokens/sec
    plus p50/p99 per-token decode latency (the PR 1 headline numbers).
  * shared-prefix — every request shares a long common prompt prefix
    (the few-shot / system-preamble pattern that dominates TPU serving
    traffic): measures the ALGORITHMIC win of prefix caching + chunked
    prefill as the prefill-token reduction (prompt tokens submitted /
    prefill tokens actually computed), with outputs asserted identical
    to the no-cache greedy reference.
  * repetitive-decode — speculative decoding's target regime: an LM
    whose greedy continuation is highly repetitive (built from the
    bench model by an "echo" weight surgery, see _make_echo_lm — the
    constructed analog of the shared-prefix workload's constructed
    sharing). Measures serve_decode_step_reduction: decode steps the
    non-speculative engine dispatches / decode steps the speculative
    engine dispatches for the SAME (asserted token-identical) outputs.
  * kv-capacity — int8 quantized KV pages at an EQUAL pool byte budget
    (kv_pool_mb sizing, so the page count follows the storage format's
    itemsize): f32 vs int8 engines run the same memory-pressure
    workload; int8's ~2.7-3.8x pages (head_dim-dependent) admit more
    concurrent sequences, so the same requests finish in fewer engine
    steps at higher decode concurrency. Gates (smoke): >= 1.9x
    effective page capacity, a concurrency AND step-count win, int8
    greedy outputs token-identical to the no-cache reference
    (the relaxed quantized-pages gate), zero recompiles.

  * shard — tensor-parallel sharded serving A/B on a forced
    multi-device host mesh (docs/serving.md "Sharded serving"): the
    same model served single-device and head-sharded over a "tensor"
    mesh must produce token-identical greedy outputs with zero
    recompiles and ~t× smaller per-device KV pool + dispatched FLOPs;
    the v5e decode-step latency per tensor degree is SIMULATED by the
    placement search (search/serve_place.optimize_serve) over a
    Gemma-31B-class arch and gated >= 1.5x at t=4 (ci.sh 1j).

Select with --workload {all,base,spec,kv,shard} (base = the first two).

Emits one BENCH-convention JSON line per workload ({"metric", "value",
"unit", "extra"}) to stdout and (by default) BENCH_serve.json next to
the other BENCH_*.json artifacts.

`--smoke` is the CI gate (tools/ci.sh steps 1d/1f): a small model,
hard asserts on (a) ZERO recompiles after warmup, (b) exactness vs
generate_reference, (c) >= 2x prefill-token reduction on the
shared-prefix workload (step 1d, --workload base), (d) >= 1.5x decode
step reduction on the repetitive workload (step 1f, --workload spec).

Runs anywhere: on CPU hosts the serve path uses the jnp gather
fallback of the paged-attention kernels (force it with --cpu), on TPU
the Pallas kernels. Usage:

    python tools/serve_bench.py                       # defaults
    python tools/serve_bench.py --requests 32 --max-new 64 --cpu
    python tools/serve_bench.py --smoke               # the CI gates
    python tools/serve_bench.py --smoke --workload spec   # 1f only
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _make_echo_lm(cfg, args):
    """A copy of the bench LM surgically rewired so greedy decode
    echoes the trailing token: attention/FFN residual writers zeroed
    (the stream is exactly tok+pos embeddings), position embeddings
    damped, and the head tied to the token embeddings — near-orthogonal
    random embeddings make each token its own argmax. Its continuation
    is the maximally repetitive text prompt-lookup drafting targets,
    giving the decode-step-reduction gate a DETERMINISTIC workload
    instead of hoping a random LM's greedy stream falls into a cycle
    (the same constructed-favorable-case trick as the shared-prefix
    workload)."""
    import jax.numpy as jnp
    from flexflow_tpu.config import CompMode
    from flexflow_tpu.models.transformer import build_transformer_lm
    ff = build_transformer_lm(
        cfg, vocab_size=args.vocab, max_seq_len=args.max_seq_len,
        hidden=args.hidden, num_heads=args.heads, num_layers=args.layers,
        ff_dim=4 * args.hidden)
    ff.compile(comp_mode=CompMode.INFERENCE)
    p = ff.state.params
    for i in range(args.layers):
        attn = p[f"layer{i}_attn"]
        attn["wo"] = jnp.zeros_like(attn["wo"])
        if "bo" in attn:
            attn["bo"] = jnp.zeros_like(attn["bo"])
        ff2 = p[f"layer{i}_ff2"]
        ff2["kernel"] = jnp.zeros_like(ff2["kernel"])
        if "bias" in ff2:
            ff2["bias"] = jnp.zeros_like(ff2["bias"])
    p["pos_embed"]["kernel"] = p["pos_embed"]["kernel"] * 0.15
    p["lm_head"]["kernel"] = 4.0 * p["tok_embed"]["kernel"].T
    if "bias" in p["lm_head"]:
        p["lm_head"]["bias"] = jnp.zeros_like(p["lm_head"]["bias"])
    return ff


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true",
                    help="force JAX_PLATFORMS=cpu before importing jax")
    ap.add_argument("--smoke", action="store_true",
                    help="small CI gate: assert zero recompiles, "
                    "exactness, >= 2x prefill reduction (base) and "
                    ">= 1.5x decode step reduction (spec)")
    ap.add_argument("--workload",
                    choices=("all", "base", "spec", "kv", "shard",
                             "telemetry", "disagg", "router", "lora",
                             "fabric", "spill", "boot", "mesh2d"),
                    default="all",
                    help="base = random + shared-prefix (ci.sh 1d), "
                    "spec = repetitive speculative decode (ci.sh 1f), "
                    "kv = int8 KV-page capacity A/B (ci.sh 1i), "
                    "shard = tensor-parallel sharded serving A/B on a "
                    "forced multi-device host mesh (ci.sh 1j), "
                    "telemetry = telemetry-on vs -off A/B gating "
                    "token identity, zero recompiles, <= 3% overhead, "
                    "trace/metrics/drift validity (ci.sh 1k), "
                    "disagg = unified vs prefill/decode-disaggregated "
                    "serving under mixed heavy-prefill + steady-decode "
                    "traffic at equal device count, gating >= 1.3x "
                    "TPOT-p99 reduction + exactness + zero recompiles "
                    "(ci.sh 1m), "
                    "router = multi-replica prefix-affinity routing "
                    "vs round-robin on a multi-tenant prefix mix "
                    "under seeded timed traffic, gating >= 1.3x "
                    "goodput-under-SLO + token exactness vs a single "
                    "replica + zero recompiles per replica + full "
                    "page reclamation, plus autoscaler determinism "
                    "(ci.sh 1n), "
                    "lora = batched multi-tenant LoRA pool vs a "
                    "sequential per-tenant weight-swap server on a "
                    "Zipf tenant mix, gating >= 1.5x goodput (mixed "
                    "steps) + token exactness vs the merged-weight "
                    "references + zero recompiles (ci.sh 1p), "
                    "fabric = wall-clock serving fabric: the same "
                    "seeded traffic on the virtual clock vs the "
                    "threaded and single-threaded wall clock, gating "
                    "token identity across all arms + >= 1.3x "
                    "threaded/single wall goodput, plus disagg "
                    "pipelined + --transport tcp token identity "
                    "(ci.sh 1q), "
                    "spill = hierarchical host-tier prefix cache on "
                    "a working-set-larger-than-pool multi-tenant "
                    "stream: host tier armed vs plain eviction vs "
                    "rung-3-style no-match, gating >= 1.3x "
                    "goodput-under-SLO over BOTH baselines + token "
                    "identity + zero recompiles + priced "
                    "spill-vs-recompute decisions (ci.sh 1r), "
                    "boot = cold vs warm replica boot A/B through the "
                    "ProgramRegistry AOT snapshot (--program-cache-dir, "
                    "core/programs.py): cold engine construction + "
                    "warmup vs one that deserializes its executables, "
                    "gating >= 2x time-to-ready reduction, ZERO "
                    "compiles + token identity on the warm arm, and "
                    "corrupt-store fallback (compile-with-warning, "
                    "never a crash) (ci.sh 1s), "
                    "mesh2d = 2-D serve-mesh placement A/B: a pool "
                    "booted from the searched (tensor degree x "
                    "replica count) vs both degenerate allocations "
                    "of the same device budget (best tp-only r=1, "
                    "best replicas-only t=1) under shared-prefix "
                    "multi-tenant traffic with the adapter pool "
                    "armed, gating >= 1.3x goodput-under-SLO over "
                    "BOTH + t=1 HBM-rejected by the search + token "
                    "identity + zero recompiles (ci.sh 1t)")
    ap.add_argument("--trace-out", default="",
                    help="write the telemetry workload's Chrome "
                    "trace-event JSON here (Perfetto-loadable; default "
                    "/tmp/flexflow_tpu_serve_trace.json)")
    ap.add_argument("--kv-dtype", default="float32",
                    choices=("float32", "bfloat16", "int8",
                             "float8_e4m3"),
                    help="KV-page storage format for the base/spec/"
                    "shard workloads (the kv workload always A/Bs f32 "
                    "vs int8 at an equal byte budget)")
    ap.add_argument("--shard-devices", type=int, default=4,
                    help="tensor-parallel degree (and forced host "
                    "device count) of the shard workload's A/B")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--hidden", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--max-seq-len", type=int, default=256)
    ap.add_argument("--max-seqs", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefix-len", type=int, default=0,
                    help="shared-prefix workload's common prefix length "
                    "(0 = half the max prompt)")
    ap.add_argument("--fault-spec", default="",
                    help="seeded fault-injection spec (utils/faults.py) "
                    "armed on the random-workload engine; also runs a "
                    "cancel/deadline storm and gates survivor "
                    "exactness + invariants + zero recompiles "
                    "(tools/ci.sh step 1g)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("-o", "--out", default="BENCH_serve.json",
                    help="output JSON path ('' = stdout only)")
    args = ap.parse_args()

    if args.cpu or args.smoke:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if args.workload in ("all", "shard", "mesh2d"):
        # the shard and mesh2d A/Bs need a multi-device host platform;
        # XLA only reads the flag at backend init, so it must be set
        # before jax imports (ci.sh steps 1j/1t also set it in the
        # environment)
        flag = (f"--xla_force_host_platform_device_count="
                f"{args.shard_devices}")
        if "xla_force_host_platform_device_count" not in \
                os.environ.get("XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                flag + " " + os.environ.get("XLA_FLAGS", ""))
    import jax
    import numpy as np

    from flexflow_tpu.utils.cache_dirs import arm_compile_cache
    arm_compile_cache()

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import build_transformer_lm
    from flexflow_tpu.serve import ServeEngine
    from flexflow_tpu.utils.profiling import serve_percentiles, serve_report

    if args.smoke:
        args.requests = 8
        args.max_new = 4
        args.vocab, args.hidden, args.layers, args.heads = 89, 32, 2, 4
        args.max_seq_len, args.max_seqs, args.page_size = 128, 4, 8

    # pages allocate on demand now, so the pool is sized for the
    # workload's ACTUAL residency (~max_seqs concurrent sequences);
    # a prefill budget of half the max length keeps long prompts
    # chunking across steps so the bench exercises that path
    pages_per_seq = -(-args.max_seq_len // args.page_size)
    cfg = FFConfig(
        batch_size=1, kv_page_size=args.page_size,
        kv_num_pages=1 + pages_per_seq * args.max_seqs,
        kv_dtype=args.kv_dtype,
        serve_max_seqs=args.max_seqs,
        serve_prefill_budget=max(args.page_size,
                                 args.max_seq_len // 2))
    ff = build_transformer_lm(
        cfg, vocab_size=args.vocab, max_seq_len=args.max_seq_len,
        hidden=args.hidden, num_heads=args.heads, num_layers=args.layers,
        ff_dim=4 * args.hidden)

    rng = np.random.RandomState(args.seed)
    max_prompt = args.max_seq_len - args.max_new
    if max_prompt < 8:
        ap.error(f"--max-seq-len ({args.max_seq_len}) must exceed "
                 f"--max-new ({args.max_new}) by at least 8 to leave "
                 f"room for prompts")
    records = []
    gates = []

    injector = None
    if args.fault_spec:
        from flexflow_tpu.utils.faults import FaultInjector
        injector = FaultInjector(args.fault_spec, seed=args.seed)

    def _assert_survivors(eng, prompts, out, ref, stats):
        """The chaos exactness contract: every COMPLETED request is
        token-identical to the reference; every aborted/rejected one's
        partial stream is a reference prefix. On lossy pools
        (--kv-dtype bfloat16/int8) both halves relax to the engine's
        tie-margin gate — the aborted half against the reference
        truncated at the abort point."""
        recs = stats["requests"]
        refs = [r if rec["outcome"] == "completed" else r[:len(o)]
                for o, r, rec in zip(out, ref, recs)]
        eng.assert_token_parity(
            prompts, out, refs,
            what="chaos survivors / aborted prefixes")
        return sum(rec["outcome"] == "completed" for rec in recs)

    if args.workload in ("all", "base"):
        # the base engine runs with the telemetry bus attached so the
        # BENCH record carries the canonical latency percentiles +
        # drift ratios (docs/observability.md); the telemetry workload
        # below is what GATES the overhead of doing so
        from flexflow_tpu.utils.telemetry import Telemetry
        base_tel = Telemetry()
        eng = ServeEngine(ff, faults=injector, telemetry=base_tel)
        t0 = time.perf_counter()
        counts = eng.warmup()
        warm_s = time.perf_counter() - t0

        # ---- workload 1: random ragged prompts (throughput) ----------
        prompts = [list(rng.randint(1, args.vocab,
                                    size=rng.randint(4, max_prompt + 1)))
                   for _ in range(args.requests)]
        t0 = time.perf_counter()
        out = eng.generate(prompts, args.max_new)
        wall = time.perf_counter() - t0
        stats = eng.last_stats
        print(serve_report(stats), file=sys.stderr)
        if injector is None:
            assert all(len(o) > 0 for o in out)
        else:
            # under injected faults the gate is survivor exactness +
            # clean invariants, not universal completion
            _assert_survivors(eng, prompts, out, eng.generate_reference(
                prompts, args.max_new), stats)
            eng.cache.check_invariants()

        pct = serve_percentiles(stats)
        records.append({
            "metric": "serve_decode_tokens_per_sec",
            "value": round(stats["tokens_per_sec"], 2),
            "unit": "tokens/s",
            "extra": {
                "platform": jax.default_backend(),
                "requests": args.requests,
                "max_new_tokens": args.max_new,
                "total_new_tokens": stats["total_new_tokens"],
                "decode_steps": stats["decode_steps"],
                "mean_decode_width": round(
                    float(np.mean(stats["decode_widths"]))
                    if stats["decode_widths"] else 0.0, 2),
                "per_token_latency_ms_p50": round(pct[50] * 1e3, 4),
                "per_token_latency_ms_p99": round(pct[99] * 1e3, 4),
                # the telemetry snapshot's latency/drift block: TTFT
                # from the same registry serve_report renders, drift =
                # measured/predicted per serve regime (the simulator
                # calibration signal)
                "telemetry": {
                    "ttft_ms_p50": round(
                        base_tel.metrics.quantile(
                            "serve_ttft_seconds", 50) * 1e3, 4),
                    "ttft_ms_p99": round(
                        base_tel.metrics.quantile(
                            "serve_ttft_seconds", 99) * 1e3, 4),
                    "tpot_ms_p50": round(
                        base_tel.metrics.quantile(
                            "serve_tpot_seconds", 50) * 1e3, 4),
                    "tpot_ms_p99": round(
                        base_tel.metrics.quantile(
                            "serve_tpot_seconds", 99) * 1e3, 4),
                    "tokens_per_sec": round(
                        base_tel.metrics.gauge("serve_tokens_per_sec"),
                        2),
                    "drift_ratio_by_regime": {
                        reg: round(d["ratio"], 2)
                        for reg, d in base_tel.drift_snapshot().get(
                            "serve", {}).items()},
                },
                "preemptions": stats["preemptions"],
                "page_util_max": round(stats["page_util_max"], 4),
                "spec_acceptance": round(stats["spec_acceptance"], 4),
                "warmup_s": round(warm_s, 2),
                "wall_s": round(wall, 2),
                "compile_counts": stats["compile_counts"],
                "model": {"vocab": args.vocab, "hidden": args.hidden,
                          "layers": args.layers, "heads": args.heads,
                          "max_seq_len": args.max_seq_len,
                          "page_size": args.page_size,
                          "max_seqs": args.max_seqs},
            },
        })

        # ---- chaos storm (only with --fault-spec): cancels + deadlines
        # through the SAME engine the injected faults hit, gating that
        # the engine is still serving exactly, reclaiming every page,
        # and never recompiling (tools/ci.sh step 1g)
        if injector is not None:
            cprompts = [list(rng.randint(
                1, args.vocab, size=rng.randint(4, max_prompt + 1)))
                for _ in range(args.requests)]
            cref = eng.generate_reference(cprompts, args.max_new)
            deadlines = [None] * args.requests
            deadlines[1 % args.requests] = 1e-9      # expires instantly
            storm = {1: [2 % args.requests], 3: [5 % args.requests]}

            def on_step(step):
                for rid in storm.get(step, ()):
                    eng.cancel(rid)
                eng.cache.check_invariants()         # after every event

            cout = eng.generate(cprompts, args.max_new,
                                deadline_s=deadlines, on_step=on_step)
            cstats = eng.last_stats
            survivors = _assert_survivors(eng, cprompts, cout, cref,
                                          cstats)
            assert survivors > 0, "chaos storm left no survivors"
            aborted = (cstats["cancelled"] + cstats["deadline_expired"]
                       + cstats["rejected"])
            assert aborted > 0, "chaos storm aborted nothing"
            assert eng.compile_counts() == counts, (
                f"chaos recompiled: {counts} -> {eng.compile_counts()}")
            assert eng.cache.free_pages == \
                eng.cache_cfg.usable_pages, "chaos leaked pages"
            retried = stats["retries"] + cstats["retries"]
            gates.append(
                f"chaos survivors={survivors} aborted={aborted} "
                f"retried={retried} "
                f"rung_max={max(stats['degradation_rung_max'], cstats['degradation_rung_max'])}")
            records.append({
                "metric": "serve_chaos_survivor_exactness",
                "value": 1.0,
                "unit": "bool",
                "extra": {
                    "platform": jax.default_backend(),
                    "fault_spec": args.fault_spec,
                    "seed": args.seed,
                    "survivors": survivors,
                    "cancelled": cstats["cancelled"],
                    "deadline_expired": cstats["deadline_expired"],
                    "rejected": cstats["rejected"],
                    "retried_dispatches": retried,
                    "degradation_rung_max": max(
                        stats["degradation_rung_max"],
                        cstats["degradation_rung_max"]),
                    "rung_steps": cstats["rung_steps"],
                    "outputs_match_reference": True,
                    "compile_counts": eng.compile_counts(),
                },
            })

        # ---- workload 2: shared prefix (the prefix-cache win) --------
        # a FRESH engine so workload 1's committed pages cannot inflate
        # the hit rate: every hit below comes from sharing inside this
        # workload (and the fault injector stays off it — its gates
        # measure the cache, not the chaos)
        eng2 = ServeEngine(ff)
        eng2.warmup()
        prefix_len = args.prefix_len or max_prompt // 2
        tail = max(4, args.page_size // 2)
        prefix = list(rng.randint(1, args.vocab, size=prefix_len))
        sprompts = [prefix + list(rng.randint(1, args.vocab, size=tail))
                    for _ in range(args.requests)]
        before = eng2.compile_counts()
        t0 = time.perf_counter()
        sout = eng2.generate(sprompts, args.max_new)
        swall = time.perf_counter() - t0
        sstats = eng2.last_stats
        print(serve_report(sstats), file=sys.stderr)
        computed = sstats["prefill_tokens_computed"]
        submitted = sstats["prompt_tokens_total"]
        reduction = submitted / computed if computed else float("inf")

        # the serving CORRECTNESS contracts hold on every run: no
        # program compiled after warmup, and the prefix-cached (and,
        # by default, speculative) outputs are exactly the no-cache
        # greedy reference
        assert eng2.compile_counts() == before, (
            f"serving recompiled: {before} -> {eng2.compile_counts()}")
        ref = eng2.generate_reference(sprompts, args.max_new)
        eng2.assert_token_parity(sprompts, sout, ref,
                                 what="prefix-cached outputs")
        # the >= 2x reduction is a property of the DEFAULT shared-prefix
        # shapes, so it hard-gates only under --smoke (CI); a custom
        # --prefix-len/--requests sweep should report, not crash
        if reduction < 2.0:
            msg = (f"prefix caching only cut prefill tokens "
                   f"{reduction:.2f}x ({computed}/{submitted}) — "
                   f"expected >= 2x on shared prefixes")
            assert not args.smoke, msg
            print(f"WARNING: {msg}", file=sys.stderr)
        gates.append(f"prefill_reduction={reduction:.2f}x "
                     f"compile_counts={counts}")

        records.append({
            "metric": "serve_prefill_token_reduction",
            "value": round(reduction, 2),
            "unit": "x",
            "extra": {
                "platform": jax.default_backend(),
                "requests": args.requests,
                "prefix_len": prefix_len,
                "tail_len": tail,
                "prompt_tokens_submitted": submitted,
                "prefill_tokens_computed": computed,
                "prefix_hit_tokens": sstats["prefix_hit_tokens"],
                "tokens_per_sec": round(sstats["tokens_per_sec"], 2),
                "outputs_match_reference": True,
                "wall_s": round(swall, 2),
                "compile_counts": sstats["compile_counts"],
            },
        })

    if args.workload in ("all", "spec"):
        # ---- workload 3: repetitive decode (speculative decoding) ----
        # one echo LM, two engines over its params: speculative (k=8)
        # vs non-speculative baseline. The win is decode STEPS — every
        # decode step is one dispatch of the same fixed-shape mixed
        # program, so steps_base / steps_spec is the dispatch-count
        # reduction for token-identical outputs.
        spec_k = 8
        prompt_hi = 17          # spec prompts draw from [4, prompt_hi)
        spec_new = min(max(24, args.max_new),
                       args.max_seq_len - prompt_hi)
        if spec_new < 8:
            ap.error(f"--max-seq-len ({args.max_seq_len}) leaves no "
                     f"room for the repetitive-decode workload "
                     f"(needs prompt + >= 8 new tokens)")
        ff_echo = _make_echo_lm(cfg, args)
        eng_s = ServeEngine(ff_echo, spec_tokens=spec_k)
        eng_s.warmup()
        eng_b = ServeEngine(ff_echo, spec_tokens=0)
        eng_b.warmup()
        rprompts = [list(rng.randint(1, args.vocab,
                                     size=rng.randint(4, prompt_hi)))
                    for _ in range(args.requests)]
        before = eng_s.compile_counts()
        t0 = time.perf_counter()
        rout = eng_s.generate(rprompts, spec_new)
        rwall = time.perf_counter() - t0
        rstats = eng_s.last_stats
        print(serve_report(rstats), file=sys.stderr)
        bout = eng_b.generate(rprompts, spec_new)
        bsteps = eng_b.last_stats["decode_steps"]
        ssteps = rstats["decode_steps"]
        step_red = bsteps / ssteps if ssteps else float("inf")

        assert eng_s.compile_counts() == before, (
            f"speculative serving recompiled: "
            f"{before} -> {eng_s.compile_counts()}")
        # speculative vs baseline is an EXACT contract at any page
        # format (both engines read the same deterministic quantized
        # content); the reference comparison relaxes for lossy formats
        assert rout == bout, (
            "speculative outputs diverged from the non-speculative "
            "engine on the same pages")
        ref = eng_s.generate_reference(rprompts, spec_new)
        eng_s.assert_token_parity(rprompts, rout, ref,
                                  what="speculative outputs")
        eng_b.assert_token_parity(rprompts, bout, ref,
                                  what="baseline outputs")
        # >= 1.5x is a property of the constructed repetitive workload
        # (echo LM + prompt-lookup drafting), hard-gated under --smoke
        if step_red < 1.5:
            msg = (f"speculative decoding only cut decode steps "
                   f"{step_red:.2f}x ({bsteps}/{ssteps}) — expected "
                   f">= 1.5x on repetitive text")
            assert not args.smoke, msg
            print(f"WARNING: {msg}", file=sys.stderr)
        gates.append(f"decode_step_reduction={step_red:.2f}x "
                     f"compile_counts={eng_s.compile_counts()}")

        records.append({
            "metric": "serve_decode_step_reduction",
            "value": round(step_red, 2),
            "unit": "x",
            "extra": {
                "platform": jax.default_backend(),
                "requests": args.requests,
                "max_new_tokens": spec_new,
                "spec_tokens": spec_k,
                "decode_steps_baseline": bsteps,
                "decode_steps_speculative": ssteps,
                "spec_drafted_tokens": rstats["spec_drafted_tokens"],
                "spec_accepted_tokens": rstats["spec_accepted_tokens"],
                "spec_acceptance": round(rstats["spec_acceptance"], 4),
                "steps_per_decode_token": round(
                    rstats["steps_per_decode_token"], 4),
                "outputs_match_reference": True,
                "wall_s": round(rwall, 2),
                "compile_counts": rstats["compile_counts"],
            },
        })

    if args.workload in ("all", "kv"):
        # ---- workload 4: int8 KV-page capacity at an equal byte
        # budget (tools/ci.sh step 1i). Two engines over identically
        # initialized models, pools sized by kv_pool_mb so the page
        # count follows the storage format's itemsize: the f32 pool is
        # deliberately TIGHT (~2.2 sequences of history) so admission
        # blocks / preemption churns, while int8's ~2.7x pages (at this
        # head_dim) run the same requests at higher decode concurrency
        # in fewer engine steps. Outputs of BOTH arms must be greedy
        # token-identical to the no-cache f32 reference — the relaxed
        # quantized-pages exactness gate.
        head_dim = args.hidden // args.heads
        kv_seqs = max(args.max_seqs, 8)
        kv_new = min(max(16, args.max_new),
                     args.max_seq_len - args.page_size)
        kv_reqs = max(12, args.requests)
        from flexflow_tpu.serve.kv_cache import KVCacheConfig
        f32_page_bytes = KVCacheConfig(
            num_layers=args.layers, num_heads=args.heads,
            head_dim=head_dim, page_size=args.page_size,
            num_pages=2, max_seqs=1).f32_page_bytes
        tight_pages = max(pages_per_seq, int(2.2 * pages_per_seq))
        budget_mb = tight_pages * f32_page_bytes / float(1 << 20)

        def kv_engine(dtype):
            c = FFConfig(
                batch_size=1, kv_page_size=args.page_size,
                kv_pool_mb=budget_mb, kv_dtype=dtype,
                serve_max_seqs=kv_seqs,
                serve_prefill_budget=max(args.page_size,
                                         args.max_seq_len // 2))
            m = build_transformer_lm(
                c, vocab_size=args.vocab, max_seq_len=args.max_seq_len,
                hidden=args.hidden, num_heads=args.heads,
                num_layers=args.layers, ff_dim=4 * args.hidden)
            # speculation off in both arms: the A/B measures the page
            # pool, and drafts would add a second page consumer
            return ServeEngine(m, spec_tokens=0)

        prompt_cap = args.max_seq_len - kv_new
        kv_prompts = [list(rng.randint(
            1, args.vocab,
            size=rng.randint(args.page_size, max(args.page_size + 1,
                                                 prompt_cap // 2))))
            for _ in range(kv_reqs)]

        arms = {}
        for dtype in ("float32", "int8"):
            eng_kv = kv_engine(dtype)
            counts_kv = eng_kv.warmup()
            t0 = time.perf_counter()
            out_kv = eng_kv.generate(kv_prompts, kv_new)
            wall_kv = time.perf_counter() - t0
            st = eng_kv.last_stats
            print(serve_report(st), file=sys.stderr)
            assert eng_kv.compile_counts() == counts_kv, (
                f"{dtype} kv arm recompiled: "
                f"{counts_kv} -> {eng_kv.compile_counts()}")
            if dtype == "int8":
                eng_kv.check_kv_scales()
            eng_kv.cache.check_invariants()
            arms[dtype] = {
                "engine": eng_kv, "out": out_kv, "stats": st,
                "wall_s": wall_kv,
                "usable_pages": eng_kv.cache_cfg.usable_pages,
                "pool_bytes": eng_kv.cache_cfg.pool_bytes,
                "steps": st["steps"],
                "mean_decode_width": (
                    float(np.mean(st["decode_widths"]))
                    if st["decode_widths"] else 0.0),
                "tokens_per_sec": st["tokens_per_sec"],
                "preemptions": st["preemptions"],
            }

        f, q = arms["float32"], arms["int8"]
        # exactness gates. f32 pages are lossless: full token identity
        # with the no-cache reference. int8 pages gate the RELAXED
        # quantized contract instead (docs/serving.md): (a) greedy
        # token parity up to tie flips on both the base-shaped and the
        # long workload (ServeEngine.assert_token_parity), with most base
        # requests fully identical, (b) token identity across chunking
        # interleavings — a different prefill budget moves every chunk
        # boundary, and per-row write-local scales must make that
        # invisible — and (c) the per-element attention-output atol
        # gated in tests/test_kv_quant.py.
        kv_ref = f["engine"].generate_reference(kv_prompts, kv_new)
        assert f["out"] == kv_ref, "f32 kv arm diverged from reference"
        base_prompts = kv_prompts[:8]
        # this untimed run doubles as the mid-run scale audit: on_step
        # fires while sequences are RESIDENT, which is the only time
        # check_kv_scales can inspect live (slot, position) rows
        base_out = q["engine"].generate(
            base_prompts, 4,
            on_step=lambda s: q["engine"].check_kv_scales())
        base_ref = q["engine"].generate_reference(base_prompts, 4)
        base_exact = q["engine"].assert_token_parity(
            base_prompts, base_out, base_ref, min_exact_frac=0.75,
            what="int8 base workload")
        long_exact = q["engine"].assert_token_parity(
            kv_prompts, q["out"], kv_ref, what="int8 long workload")
        eng_alt = kv_engine("int8")
        eng_alt.prefill_budget = max(args.page_size,
                                     eng_alt.prefill_budget // 3)
        eng_alt.mixed_width = (eng_alt.prefill_budget
                               + eng_alt.cache_cfg.max_seqs)
        eng_alt.warmup()
        alt_out = eng_alt.generate(kv_prompts, kv_new)
        assert alt_out == q["out"], (
            "int8 outputs changed across chunking interleavings — "
            "quantized content must be chunk-boundary invariant")
        agree = sum(
            len(o) if d is None else d
            for o, r in zip(q["out"], kv_ref)
            for d in (ServeEngine.first_divergence(o, r),))
        total_ref = sum(len(o) for o in q["out"])
        capacity = q["usable_pages"] / f["usable_pages"]
        concurrency = (q["mean_decode_width"]
                       / max(f["mean_decode_width"], 1e-9))
        step_ratio = f["steps"] / max(q["steps"], 1)
        tput_ratio = (q["tokens_per_sec"]
                      / max(f["tokens_per_sec"], 1e-9))
        if capacity < 1.9 or concurrency <= 1.0 or step_ratio <= 1.0:
            msg = (f"int8 kv pages: capacity {capacity:.2f}x "
                   f"(want >= 1.9), concurrency {concurrency:.2f}x, "
                   f"steps {step_ratio:.2f}x (want > 1.0 each)")
            assert not args.smoke, msg
            print(f"WARNING: {msg}", file=sys.stderr)
        gates.append(f"kv_capacity={capacity:.2f}x "
                     f"concurrency={concurrency:.2f}x "
                     f"steps={step_ratio:.2f}x")

        records.append({
            "metric": "serve_kv_page_capacity",
            "value": round(capacity, 2),
            "unit": "x",
            "extra": {
                "platform": jax.default_backend(),
                "pool_budget_mb": round(budget_mb, 3),
                "requests": kv_reqs,
                "max_new_tokens": kv_new,
                "head_dim": head_dim,
                "pages_f32": f["usable_pages"],
                "pages_int8": q["usable_pages"],
                "pool_bytes_f32": f["pool_bytes"],
                "pool_bytes_int8": q["pool_bytes"],
                "steps_f32": f["steps"],
                "steps_int8": q["steps"],
                "step_reduction": round(step_ratio, 2),
                "mean_decode_width_f32": round(
                    f["mean_decode_width"], 2),
                "mean_decode_width_int8": round(
                    q["mean_decode_width"], 2),
                "concurrency_gain": round(concurrency, 2),
                "tokens_per_sec_f32": round(f["tokens_per_sec"], 2),
                "tokens_per_sec_int8": round(q["tokens_per_sec"], 2),
                "throughput_gain": round(tput_ratio, 2),
                "preemptions_f32": f["preemptions"],
                "preemptions_int8": q["preemptions"],
                "greedy_parity_base_exact": f"{base_exact}/"
                                            f"{len(base_prompts)}",
                "greedy_parity_long_exact": f"{long_exact}/"
                                            f"{len(kv_prompts)}",
                "chunking_invariant": True,
                "prefix_agreement_long_stream": round(
                    agree / max(total_ref, 1), 4),
                "attn_block_kv": q["stats"]["kv_pool"]["attn_block_kv"],
                "attn_dispatch_passes": q["stats"]["kv_pool"][
                    "attn_dispatch_passes"],
            },
        })

    if args.workload in ("all", "shard"):
        # ---- workload 5: tensor-parallel sharded serving (ci.sh 1j).
        # A/B on the forced multi-device host mesh: the SAME model
        # served by a single-device engine and a head-sharded
        # tensor-parallel engine — outputs must be token-identical on
        # f32 pages (tie-margin parity on quantized), zero recompiles,
        # per-device dispatched FLOPs and pool bytes reduced ~t×. The
        # measured A/B proves correctness on the CPU mesh; the SPEED
        # story is simulated on the v5e machine model by the placement
        # search (search/serve_place.optimize_serve) over a
        # production-scale arch — the PAPERS.md Gemma-31B-class
        # serving comparison — which is what the >= 1.5x decode-step
        # speedup gate at t=4 reads.
        t_deg = args.shard_devices
        ndev = len(jax.devices())
        shard_skip = None
        if t_deg < 2:
            # a t=1 "sharded" engine has no sharding block to report
            # and nothing to A/B against
            shard_skip = (f"--shard-devices ({t_deg}) must be >= 2 "
                          f"for the sharded-vs-single A/B")
        elif ndev < t_deg:
            # XLA_FLAGS only forces extra devices on the CPU host
            # platform, so a 1-chip TPU/GPU lands here under the
            # default --workload all: SKIP the A/B (keeping the other
            # workloads' records) unless shard was asked for by name
            shard_skip = (f"shard workload needs {t_deg} devices, "
                          f"have {ndev} (set XLA_FLAGS="
                          f"--xla_force_host_platform_device_count="
                          f"{t_deg})")
        elif args.heads % t_deg:
            shard_skip = (f"--heads ({args.heads}) must divide by "
                          f"--shard-devices ({t_deg})")
        if shard_skip and args.workload == "shard":
            ap.error(shard_skip)
        if shard_skip:
            print(f"WARNING: skipping shard workload: {shard_skip}",
                  file=sys.stderr)
    if args.workload in ("all", "shard") and not shard_skip:
        eng_u = ServeEngine(ff)
        cnt_u = eng_u.warmup()
        eng_t = ServeEngine(ff, tensor_parallel=t_deg)
        cnt_t = eng_t.warmup()
        hprompts = [list(rng.randint(
            1, args.vocab, size=rng.randint(4, max_prompt + 1)))
            for _ in range(args.requests)]
        t0 = time.perf_counter()
        out_u = eng_u.generate(hprompts, args.max_new)
        wall_u = time.perf_counter() - t0
        t0 = time.perf_counter()
        out_t = eng_t.generate(hprompts, args.max_new)
        wall_t = time.perf_counter() - t0
        tstats = eng_t.last_stats
        print(serve_report(tstats), file=sys.stderr)
        assert eng_u.compile_counts() == cnt_u and \
            eng_t.compile_counts() == cnt_t, (
                f"shard A/B recompiled: {cnt_u}/{cnt_t} -> "
                f"{eng_u.compile_counts()}/{eng_t.compile_counts()}")
        # sharded vs single-device is an EXACT contract at any page
        # format (per-head bit identity + exact psums); the reference
        # comparison relaxes for lossy formats as usual
        assert out_t == out_u, (
            "sharded outputs diverged from the single-device engine")
        eng_t.assert_token_parity(
            hprompts, out_t,
            eng_u.generate_reference(hprompts, args.max_new),
            what="sharded outputs")
        eng_t.cache.check_invariants()
        sh = tstats["sharding"]
        cfg_t = eng_t.cache_cfg
        # per-device reductions: pool bytes divide exactly by t (head
        # sharding carries the whole page), dispatched matmul/attention
        # FLOPs divide by t up to the replicated LN/residual tail
        pool_ratio = cfg_t.page_bytes / cfg_t.page_device_bytes
        assert pool_ratio == t_deg, (
            f"pool bytes/device reduced {pool_ratio}x, want {t_deg}x")
        # per-device dispatched FLOPs, MEASURED by XLA's cost analysis
        # of the two compiled mixed programs (the sharded one is the
        # per-device program) — not the analytic /t formula this gate
        # exists to check. Ratio < t by the replicated LN/residual/
        # sampling tail; a lost /t anywhere would collapse it to ~1.
        ca_u = eng_u.mixed_step_cost_analysis()
        ca_t = eng_t.mixed_step_cost_analysis()
        flops_ratio = None
        if ca_u and ca_t and ca_u.get("flops") and ca_t.get("flops"):
            flops_ratio = ca_u["flops"] / ca_t["flops"]
            assert flops_ratio >= 0.6 * t_deg, (
                f"per-device mixed-step FLOPs only reduced "
                f"{flops_ratio:.2f}x at t={t_deg} (want >= "
                f"{0.6 * t_deg:.1f}x)")
        elif args.smoke:
            raise AssertionError(
                "backend cost analysis unavailable: the smoke gate "
                "cannot measure the per-device FLOPs reduction")

        # the simulated v5e story: the placement search prices the
        # mixed decode step per tensor degree for (a) a Gemma-31B-class
        # serving arch — too big for one v5e chip, the PAPERS.md
        # comparison — and (b) this bench's tiny model, where the
        # search correctly keeps t=1 (collectives would dominate)
        from flexflow_tpu.parallel.mesh import MachineSpec
        from flexflow_tpu.search.cost_model import ServeArch
        from flexflow_tpu.search.machine_model import TPUMachineModel
        from flexflow_tpu.search.serve_place import optimize_serve
        big = ServeArch(
            num_layers=48, hidden=6144, num_heads=48, head_dim=128,
            ff_dim=24576, vocab=256128, decode_lanes=32,
            prefill_lanes=512, context=2048,
            kv_dtype="int8", kv_itemsize=1.0, kv_scales=True,
            act_itemsize=2.0, act_dtype="bfloat16", param_itemsize=2.0)
        mm = TPUMachineModel(spec=MachineSpec.v5e(8))
        place = optimize_serve(big, 8, mm=mm)
        table = place.decode_by_degree
        speedup4 = table[1] / table[4]
        tiny_place = optimize_serve(eng_t.serve_arch(), 8, mm=mm)
        if speedup4 < 1.5:
            msg = (f"simulated v5e decode step at t=4 only "
                   f"{speedup4:.2f}x faster than t=1 (want >= 1.5x)")
            assert not args.smoke, msg
            print(f"WARNING: {msg}", file=sys.stderr)
        flops_txt = ("n/a" if flops_ratio is None
                     else f"{flops_ratio:.2f}x")
        gates.append(
            f"shard parity ok, pool/device {pool_ratio:.0f}x, "
            f"flops/device {flops_txt}, sim_speedup(t=4)="
            f"{speedup4:.2f}x, auto_t={place.tensor_parallel}")

        records.append({
            "metric": "serve_shard_decode_speedup",
            "value": round(speedup4, 2),
            "unit": "x",
            "extra": {
                "platform": jax.default_backend(),
                "tensor_parallel": t_deg,
                "requests": args.requests,
                "max_new_tokens": args.max_new,
                "outputs_match_single_device": True,
                "outputs_match_reference": True,
                "compile_counts": eng_t.compile_counts(),
                "heads_per_device": sh["heads_per_device"],
                "kv_pool_device_bytes": sh["kv_pool_device_bytes"],
                "pool_bytes_per_device_reduction": round(pool_ratio, 2),
                "flops_per_device_reduction": (
                    None if flops_ratio is None else
                    round(flops_ratio, 2)),
                "collective_bytes_per_step": sh[
                    "collective_bytes_per_step"],
                "wall_s_single": round(wall_u, 2),
                "wall_s_sharded": round(wall_t, 2),
                # simulated v5e decode-step latency per tensor degree
                # (the SOAP search applied to inference placement)
                "sim_machine": "v5e",
                "sim_arch": "gemma-31b-class int8-kv bf16",
                "sim_decode_ms_by_degree": {
                    str(t): round(d * 1e3, 3) for t, d in table.items()},
                "sim_auto_placement": {
                    "tensor_parallel": place.tensor_parallel,
                    "axis_dims": list(place.axis_dims),
                    "decode_step_ms": round(
                        place.decode_step_s * 1e3, 3)},
                "sim_bench_model_auto_t": tiny_place.tensor_parallel,
                "cost_cache_fingerprint": place.fingerprint,
            },
        })

    if args.workload in ("all", "disagg"):
        # ---- workload 7: disaggregated prefill/decode serving (ci.sh
        # step 1m, docs/serving.md "Disaggregated serving"). Mixed
        # traffic — heavy-prefill requests (long prompts, few tokens)
        # interleaved with steady decoders (short prompts, long
        # outputs) — served by (a) ONE unified mixed engine and (b) a
        # DisaggCluster at the same device count, whose decode role
        # runs a program with only a page-sized prefill stub. The
        # unified engine's fixed-width program makes every decode step
        # pay the full prefill budget's lanes; the decode role's step
        # is ~(budget/stub)x narrower, so per-token decode latency
        # (TPOT) p99 drops. Gates (smoke): disaggregated outputs
        # token-identical to the unified engine (the handoff contract;
        # reference parity relaxes on lossy pools as usual), zero
        # recompiles on every role after DisaggCluster.warmup(), and
        # >= 1.3x TPOT-p99 reduction — measured on this host OR
        # simulated by the ratio search on the v5e machine model for
        # the Gemma-31B-class arch (CPU wall clocks at toy widths are
        # noisy; the simulated number is the production claim and the
        # measured one the mechanism check — both are recorded).
        from flexflow_tpu.serve.disagg import DisaggCluster
        from flexflow_tpu.utils.profiling import disagg_report

        d_heavy = max(4, args.requests // 2)
        d_steady = max(4, args.requests // 2)
        steady_new = min(24, args.max_seq_len // 4)
        heavy_lo = max(8, int(max_prompt * 0.6))
        dprompts = []
        dnew = []
        for i in range(d_heavy + d_steady):
            if i % 2 == 0:     # heavy prefill: long prompt, FEW tokens
                # (capped so the heavy class stays prefill-dominated
                # in non-smoke runs too — the traffic shape the
                # metric's label claims)
                dprompts.append(list(rng.randint(
                    1, args.vocab,
                    size=rng.randint(heavy_lo, max_prompt + 1))))
                dnew.append(min(4, args.max_new))
            else:              # steady decode: short prompt, long output
                dprompts.append(list(rng.randint(
                    1, args.vocab, size=rng.randint(4, 17))))
                dnew.append(steady_new)

        eng_m = ServeEngine(ff, spec_tokens=0)
        cnt_m = eng_m.warmup()
        t0 = time.perf_counter()
        out_m = eng_m.generate(dprompts, dnew)
        wall_m = time.perf_counter() - t0
        mstats = eng_m.last_stats
        print(serve_report(mstats), file=sys.stderr)

        cl = DisaggCluster(ff, spec_tokens=0)
        cnt_d = cl.warmup()
        t0 = time.perf_counter()
        out_d = cl.generate(dprompts, dnew)
        wall_d = time.perf_counter() - t0
        print(disagg_report(cl.last_stats, cl.metrics),
              file=sys.stderr)

        # exactness: the cluster is token-identical to the unified
        # engine at ANY page format (the handoff moves bit-equal
        # rows); the no-cache reference comparison relaxes for lossy
        # formats through the usual tie-margin gate
        assert out_d == out_m, (
            "disaggregated outputs diverged from the unified engine")
        dref = eng_m.generate_reference(dprompts, dnew)
        eng_m.assert_token_parity(dprompts, out_d, dref,
                                  what="disaggregated outputs")
        assert eng_m.compile_counts() == cnt_m, (
            f"unified arm recompiled: {cnt_m} -> "
            f"{eng_m.compile_counts()}")
        assert cl.compile_counts() == cnt_d, (
            f"disagg cluster recompiled: {cnt_d} -> "
            f"{cl.compile_counts()}")
        cl.check_invariants()
        assert cl.stats["handoff_requests"] > 0, (
            "no pages crossed the handoff link")

        # measured TPOT p99: unified = the canonical fold over its
        # stats; disagg = the decode ROLE's role-labeled histogram
        # (the cluster's own registry — the per-role split satellite)
        uni_p99 = serve_percentiles(mstats, qs=(99,))[99]
        dec_p99 = cl.metrics.quantile("serve_tpot_seconds", 99,
                                      role="decode")
        measured = uni_p99 / dec_p99 if dec_p99 else 0.0

        # simulated: the ratio search over the Gemma-31B-class arch on
        # a 16-chip v5e — big enough that both roles fit at t=8 — with
        # the page-handoff link priced on the host link
        from flexflow_tpu.parallel.mesh import MachineSpec
        from flexflow_tpu.search.cost_model import ServeArch
        from flexflow_tpu.search.machine_model import TPUMachineModel
        from flexflow_tpu.search.serve_place import optimize_serve
        big = ServeArch(
            num_layers=48, hidden=6144, num_heads=48, head_dim=128,
            ff_dim=24576, vocab=256128, decode_lanes=32,
            prefill_lanes=512, context=2048, decode_tokens=128,
            kv_dtype="int8", kv_itemsize=1.0, kv_scales=True,
            act_itemsize=2.0, act_dtype="bfloat16",
            param_itemsize=2.0)
        mm = TPUMachineModel(spec=MachineSpec.v5e(16))
        dplace = optimize_serve(big, 16, mm=mm, disaggregated=True)
        simulated = dplace.tpot_reduction_vs_unified()

        reduction = max(measured, simulated)
        if reduction < 1.3:
            msg = (f"disaggregation only cut TPOT p99 "
                   f"{measured:.2f}x measured / {simulated:.2f}x "
                   f"simulated — expected >= 1.3x on mixed traffic")
            assert not args.smoke, msg
            print(f"WARNING: {msg}", file=sys.stderr)
        gates.append(
            f"disagg_tpot_p99_reduction={measured:.2f}x measured / "
            f"{simulated:.2f}x simulated, ratio={dplace.ratio} "
            f"(t_pre={dplace.prefill_tensor} "
            f"t_dec={dplace.decode_tensor})")

        records.append({
            "metric": "serve_disagg_tpot_p99_reduction",
            "value": round(reduction, 2),
            "unit": "x",
            "extra": {
                "platform": jax.default_backend(),
                "requests": len(dprompts),
                "heavy_prefill_requests": d_heavy,
                "steady_decode_requests": d_steady,
                "unified_tpot_ms_p99": round(uni_p99 * 1e3, 4),
                "disagg_decode_tpot_ms_p99": round(dec_p99 * 1e3, 4),
                "measured_reduction": round(measured, 2),
                "outputs_match_unified": True,
                "outputs_match_reference": True,
                "zero_recompiles": True,
                "decode_budget_lanes": cl.decode_budget,
                "unified_mixed_width": eng_m.mixed_width,
                "disagg_decode_width": cl.decode[0].mixed_width,
                "handoff": {k: round(v, 6) if isinstance(v, float)
                            else v for k, v in cl.stats.items()},
                "wall_s_unified": round(wall_m, 2),
                "wall_s_disagg": round(wall_d, 2),
                # the search's production story: simulated v5e ratio
                # table + per-role degrees + priced transfer link
                "sim_machine": "v5e-16",
                "sim_arch": "gemma-31b-class int8-kv bf16",
                "sim_tpot_reduction": round(simulated, 2),
                "sim_ratio": dplace.ratio,
                "sim_prefill_tensor": dplace.prefill_tensor,
                "sim_decode_tensor": dplace.decode_tensor,
                "sim_decode_step_ms": round(
                    dplace.decode_step_s * 1e3, 3),
                "sim_unified_tpot_ms": round(
                    dplace.unified_tpot_s * 1e3, 3),
                "sim_transfer_ms_per_request": round(
                    dplace.transfer_s * 1e3, 3),
                # the search's ratio table is already numerically
                # ordered (1:1, 1:2, ... — dict order is meaningful)
                "sim_ratio_table_ms": {
                    r: round(v * 1e3, 2)
                    for r, v in list(dplace.ratio_table.items())[:12]},
                "cost_cache_fingerprint": dplace.fingerprint,
            },
        })

    if args.workload in ("all", "router"):
        # ---- workload 8: multi-replica routing A/B (tools/ci.sh step
        # 1n, docs/serving.md "Multi-replica routing"). A simulated
        # cluster of 3 ServeEngine replicas serves the SAME seeded
        # multi-tenant traffic stream (serve/traffic.py: Poisson
        # arrivals, Zipf tenants over shared prefixes, heavy-tailed
        # tails/outputs, mid-generation cancels, seeded top-k
        # sampling) twice: prefix-affinity routed vs round-robin.
        # The geometry makes the structural argument: each replica's
        # page pool is too small to MIRROR every tenant's prefix, so
        # round-robin thrashes the prefix caches (every replica keeps
        # re-prefilling every tenant) while affinity PARTITIONS
        # tenants across replicas and hits stay hits — the aggregate-
        # cache-capacity win that decides TTFT at scale. Virtual time
        # is priced by the same cost stack the placement search uses
        # (simulate_serve_step per step), so goodput-under-SLO
        # (requests meeting both the TTFT and TPOT targets, per
        # second) is deterministic at one seed. Gates (smoke):
        # >= 1.3x affinity/round-robin goodput, every completed
        # request token-identical to ONE reference engine serving the
        # same stream ids (greedy AND sampled), zero recompiles per
        # replica after its own warmup, full page reclamation after
        # drain, and autoscaler decisions that replay identically.
        from flexflow_tpu.serve.router import Autoscaler, ReplicaPool
        from flexflow_tpu.serve.traffic import TrafficSpec, make_traffic
        from flexflow_tpu.utils.profiling import router_report
        from flexflow_tpu.utils.telemetry import Telemetry

        r_ps = 8
        r_cfg = FFConfig(
            batch_size=1, kv_page_size=r_ps, kv_num_pages=1 + 40,
            serve_max_seqs=4, serve_prefill_budget=r_ps,
            serve_spec_decode=False)
        r_ff = build_transformer_lm(
            r_cfg, vocab_size=args.vocab, max_seq_len=128,
            hidden=args.hidden, num_heads=args.heads,
            num_layers=args.layers, ff_dim=4 * args.hidden)
        r_reqs = max(48, args.requests)
        r_replicas = 3

        r_tel = Telemetry()
        pool_aff = ReplicaPool(r_ff, r_replicas, policy="affinity",
                               telemetry=r_tel)
        # every rate/SLO below is a multiple of the PRICED step, so
        # the workload scales with the engine instead of hardcoding
        # seconds (the same simulate_serve_step the search prices)
        price = pool_aff.price_probe(64)
        slo_ttft_s = 6.0 * price   # an affinity hit prefills in ~2
        slo_tpot_s = 2.0 * price   # steps; a cold 80-token prefix
        #                            needs ~10 + queueing
        spec = TrafficSpec(
            requests=r_reqs, seed=args.seed + 1, arrival="poisson",
            rate_rps=0.3 / price, tenants=6, prefix_tokens=80,
            tail_mean=5.0, output_mean=6.0, max_prompt=96,
            max_new_cap=12, cancel_frac=0.06, sample_frac=0.25,
            top_k=4, vocab=args.vocab)
        traffic = make_traffic(spec)

        res_aff = pool_aff.run(traffic, slo_ttft_s=slo_ttft_s,
                               slo_tpot_s=slo_tpot_s,
                               sample_seed=args.seed)
        print(router_report(res_aff, pool_aff.metrics),
              file=sys.stderr)
        pool_aff.assert_zero_recompiles()
        pool_aff.check_drained()

        pool_rr = ReplicaPool(r_ff, r_replicas, policy="round_robin")
        res_rr = pool_rr.run(traffic, slo_ttft_s=slo_ttft_s,
                             slo_tpot_s=slo_tpot_s,
                             sample_seed=args.seed)
        pool_rr.assert_zero_recompiles()
        pool_rr.check_drained()

        # token exactness vs a SINGLE replica serving the same stream
        # ids: completed requests identical, aborted ones a prefix —
        # for every routed arm (routing must never change tokens)
        ref_eng = ServeEngine(r_ff, spec_tokens=0)
        ref_eng.warmup()
        ref = ref_eng.generate(
            [t.prompt for t in traffic],
            [t.max_new for t in traffic],
            temperature=[t.temperature for t in traffic],
            top_k=[t.top_k for t in traffic],
            sample_seed=args.seed,
            stream_ids=[t.stream_id for t in traffic])
        for arm, res in (("affinity", res_aff),
                         ("round_robin", res_rr)):
            for rec, r in zip(res["requests"], ref):
                if rec["outcome"] == "completed":
                    assert rec["tokens"] == r, (
                        f"{arm} stream {rec['stream_id']} diverged "
                        f"from the single-replica reference")
                else:
                    assert rec["tokens"] == r[:len(rec["tokens"])], (
                        f"{arm} aborted stream {rec['stream_id']} is "
                        f"not a reference prefix")
        # traffic-shape sanity: hard under --smoke (the CI seed is
        # pinned), a warning on custom-seed sweeps — a seed whose
        # draws happen not to cancel/sample must not abort the bench
        for ok, msg in (
                (any(rec["sampled"] and rec["outcome"] == "completed"
                     for rec in res_aff["requests"]),
                 "the exactness gate never saw a completed SAMPLED "
                 "stream"),
                (res_aff["cancelled"] > 0,
                 "the cancel path never fired — cancel_frac too low")):
            if not ok:
                assert not args.smoke, msg
                print(f"WARNING: {msg}", file=sys.stderr)

        gain = (res_aff["goodput_per_s"]
                / max(res_rr["goodput_per_s"], 1e-12))
        if gain < 1.3:
            msg = (f"prefix-affinity routing only {gain:.2f}x "
                   f"round-robin goodput-under-SLO (want >= 1.3x)")
            assert not args.smoke, msg
            print(f"WARNING: {msg}", file=sys.stderr)

        # ---- autoscaler: a 1-replica pool under a seeded BURSTY
        # stream must scale up (decisions read only exported gauges,
        # priced by the search's per-degree decode table), emit spans,
        # and REPLAY identically — run twice, compare decision lists
        try:
            from flexflow_tpu.search.serve_place import optimize_serve
            table = optimize_serve(
                pool_rr.replicas[0].engine.serve_arch(), 1,
                config=r_cfg).decode_by_degree
        except Exception:
            table = None
        bspec = TrafficSpec(
            requests=r_reqs, seed=args.seed + 2, arrival="bursty",
            rate_rps=0.15 / price, burst_factor=6.0, tenants=6,
            prefix_tokens=80, tail_mean=5.0, output_mean=8.0,
            max_prompt=96, max_new_cap=16, vocab=args.vocab)
        btraffic = make_traffic(bspec)
        runs = []
        scale_tel = None
        for _trial in range(2):
            scale_tel = Telemetry()
            pool_a = ReplicaPool(r_ff, 1, policy="affinity",
                                 telemetry=scale_tel)
            scaler = Autoscaler(
                pool_a.metrics, slo_ttft_s=slo_ttft_s,
                slo_tpot_s=slo_tpot_s, min_replicas=1,
                max_replicas=2, interval_s=20 * price,
                up_patience=2, down_patience=6,
                cooldown_s=40 * price, decode_table=table,
                tensor_parallel=1,
                decode_lanes=r_cfg.serve_max_seqs)
            res_a = pool_a.run(btraffic, slo_ttft_s=slo_ttft_s,
                               slo_tpot_s=slo_tpot_s,
                               autoscaler=scaler,
                               sample_seed=args.seed)
            pool_a.assert_zero_recompiles()
            pool_a.check_drained()
            runs.append([(round(e["t"], 9), e["direction"],
                          e["replica"]) for e in res_a["scale_events"]])
            pool_a.close()
        assert runs[0] == runs[1], (
            f"autoscaler decisions did not replay: {runs[0]} vs "
            f"{runs[1]}")
        assert runs[0], "the bursty stream never triggered a scale-up"
        scale_spans = [e for e in scale_tel.events
                       if e[0] == "X" and e[2].startswith("scale_")]
        assert scale_spans, "scale events emitted no telemetry spans"

        gates.append(
            f"router_goodput_gain={gain:.2f}x "
            f"(aff {res_aff['goodput_per_s']:.0f}/s att "
            f"{res_aff['slo_attainment']:.2f} vs rr "
            f"{res_rr['goodput_per_s']:.0f}/s att "
            f"{res_rr['slo_attainment']:.2f}), autoscale "
            f"{len(runs[0])} deterministic decisions")

        records.append({
            "metric": "serve_router_goodput_gain",
            "value": round(gain, 2),
            "unit": "x",
            "extra": {
                "platform": jax.default_backend(),
                "requests": r_reqs,
                "replicas": r_replicas,
                "tenants": spec.tenants,
                "prefix_tokens": spec.prefix_tokens,
                "priced_step_ms": round(price * 1e3, 6),
                "slo_ttft_steps": 6.0, "slo_tpot_steps": 2.0,
                "goodput_affinity_per_s": round(
                    res_aff["goodput_per_s"], 2),
                "goodput_round_robin_per_s": round(
                    res_rr["goodput_per_s"], 2),
                "slo_attainment_affinity": round(
                    res_aff["slo_attainment"], 4),
                "slo_attainment_round_robin": round(
                    res_rr["slo_attainment"], 4),
                # the EXPORTED error-budget attainment gauge (read
                # back from the pool registry, not re-derived from
                # stat strings) + the burn monitor's replayable alert
                # transitions and the pool-level latency attribution
                # fold — what tools/perf_report.py renders from
                "slo_attainment_gauge": round(
                    pool_aff.metrics.gauge("serve_pool_slo_attainment",
                                           1.0), 4),
                "slo_alert_transitions": len(
                    res_aff.get("slo_alerts") or []),
                "latency_attribution_s": {
                    c: round(v, 6) for c, v in
                    (res_aff.get("attribution") or {}).items()},
                "affinity_hits": res_aff["routing"]["affinity_hits"],
                "fallbacks": res_aff["routing"]["fallbacks"],
                "spills": res_aff["routing"]["spills"],
                "cancelled": res_aff["cancelled"],
                "sampled_requests": sum(
                    1 for t in traffic if t.sampled),
                "outputs_match_single_replica": True,
                "zero_recompiles": True,
                "pages_reclaimed": True,
                "compile_counts": pool_aff.compile_counts(),
                "autoscale_events": runs[0],
                "autoscale_priced_by_decode_table": table is not None,
                "virtual_makespan_ms_affinity": round(
                    res_aff["makespan_s"] * 1e3, 4),
                "virtual_makespan_ms_round_robin": round(
                    res_rr["makespan_s"] * 1e3, 4),
            },
        })
        pool_aff.close()
        pool_rr.close()

    if args.workload in ("all", "fabric"):
        # ---- workload 9: wall-clock concurrent serving fabric
        # (tools/ci.sh step 1q, docs/serving.md "Wall-clock mode").
        # The SAME seeded, cancel-free traffic stream serves three
        # times on a 2-replica pool: on the virtual clock (the
        # deterministic authority every other workload gates on), on
        # the threaded wall clock (each replica stepping its session
        # on its own worker thread), and on the single-threaded wall
        # baseline. Sampling keys on stream ids, never on the clock,
        # so all three arms must be TOKEN-IDENTICAL — the property
        # that makes the wall twin debuggable by virtual replay.
        # Goodput-under-SLO becomes a measured wall number; the
        # threaded arm must clear >= 1.3x the single-threaded one
        # (per-step device dwell overlaps across replicas — on a
        # 1-core CI host `dwell_s` models the device time a real
        # accelerator spends off-host, which is exactly the time
        # threading overlaps). The disaggregated cluster rides along:
        # continuous pipelined generation and the --transport tcp
        # loopback socket must both match the phased in-process
        # handoff token-for-token.
        from flexflow_tpu.serve import DisaggCluster
        from flexflow_tpu.serve.router import ReplicaPool
        from flexflow_tpu.serve.traffic import TrafficSpec, make_traffic

        f_ps = 8
        f_cfg = FFConfig(
            batch_size=1, kv_page_size=f_ps, kv_num_pages=1 + 40,
            serve_max_seqs=4, serve_prefill_budget=2 * f_ps,
            serve_spec_decode=False)
        f_ff = build_transformer_lm(
            f_cfg, vocab_size=args.vocab, max_seq_len=128,
            hidden=args.hidden, num_heads=args.heads,
            num_layers=args.layers, ff_dim=4 * args.hidden)
        f_reqs = max(24, args.requests)
        f_replicas = 2
        f_dwell = 0.008           # per-step wall floor (device dwell)
        f_scale = 0.1             # arrival compression: load-bound

        pool_v = ReplicaPool(f_ff, f_replicas, policy="affinity")
        price = pool_v.price_probe(64)
        fspec = TrafficSpec(
            requests=f_reqs, seed=args.seed + 3, arrival="poisson",
            rate_rps=0.3 / price, tenants=4, prefix_tokens=24,
            tail_mean=5.0, output_mean=6.0, max_prompt=64,
            max_new_cap=8, cancel_frac=0.0, sample_frac=0.25,
            top_k=4, vocab=args.vocab)
        ftraffic = make_traffic(fspec)
        step_wall = f_dwell + price        # one dispatched wall step
        f_ttft = 40.0 * step_wall
        f_tpot = 6.0 * step_wall

        def _toks(res):
            return {r["stream_id"]: r["tokens"]
                    for r in res["requests"]}

        res_v = pool_v.run(ftraffic, slo_ttft_s=6.0 * price,
                           slo_tpot_s=2.0 * price,
                           sample_seed=args.seed)
        pool_v.assert_zero_recompiles()
        pool_v.check_drained()
        pool_v.close()

        pool_t = ReplicaPool(f_ff, f_replicas, policy="affinity")
        res_t = pool_t.run(ftraffic, slo_ttft_s=f_ttft,
                           slo_tpot_s=f_tpot, sample_seed=args.seed,
                           wall_clock=True, wall_threads=True,
                           time_scale=f_scale, dwell_s=f_dwell)
        pool_t.assert_zero_recompiles()
        pool_t.check_drained()
        pool_t.close()

        pool_s = ReplicaPool(f_ff, f_replicas, policy="affinity")
        res_s = pool_s.run(ftraffic, slo_ttft_s=f_ttft,
                           slo_tpot_s=f_tpot, sample_seed=args.seed,
                           wall_clock=True, wall_threads=False,
                           time_scale=f_scale, dwell_s=f_dwell)
        pool_s.assert_zero_recompiles()
        pool_s.check_drained()
        pool_s.close()

        # THE identity gate: wall == virtual, token for token, at one
        # seed — threaded interleaving and wall pacing change when
        # steps run, never what they compute
        assert _toks(res_t) == _toks(res_v), (
            "threaded wall-clock run diverged from the virtual-clock "
            "replay of the same traffic")
        assert _toks(res_s) == _toks(res_v), (
            "single-threaded wall-clock run diverged from the "
            "virtual-clock replay")
        assert res_t["clock"] == "wall" and res_t["wall_threads"]
        assert res_s["clock"] == "wall" and not res_s["wall_threads"]

        wall_gain = (res_t["goodput_per_s"]
                     / max(res_s["goodput_per_s"], 1e-12))
        if wall_gain < 1.3:
            msg = (f"threaded wall goodput only {wall_gain:.2f}x the "
                   f"single-threaded baseline (want >= 1.3x)")
            assert not args.smoke, msg
            print(f"WARNING: {msg}", file=sys.stderr)

        # ---- disagg: continuous pipelining + cross-process shipment
        d_cfg = FFConfig(
            batch_size=1, kv_page_size=f_ps, kv_num_pages=1 + 64,
            serve_max_seqs=4, serve_prefill_budget=4 * f_ps,
            serve_spec_decode=False)
        d_ff = build_transformer_lm(
            d_cfg, vocab_size=args.vocab, max_seq_len=128,
            hidden=args.hidden, num_heads=args.heads,
            num_layers=args.layers, ff_dim=4 * args.hidden)
        d_prompts = [list(rng.randint(1, args.vocab,
                                      size=rng.randint(8, 41)))
                     for _ in range(6)]
        d_new = [int(x) for x in rng.randint(2, 7, size=6)]
        d_temps = [0.8 if i % 2 == 0 else None for i in range(6)]
        d_tks = [4 if i % 2 == 0 else None for i in range(6)]
        with DisaggCluster(d_ff) as d_cl:
            d_ref = d_cl.generate(d_prompts, d_new,
                                  temperature=d_temps, top_k=d_tks,
                                  sample_seed=args.seed)
            d_piped = d_cl.generate_pipelined(
                d_prompts, d_new, temperature=d_temps, top_k=d_tks,
                sample_seed=args.seed)
            assert d_piped == d_ref, (
                "pipelined disagg diverged from the phased path")
        d_ff_tcp = build_transformer_lm(
            dataclasses.replace(d_cfg, serve_transport="tcp"),
            vocab_size=args.vocab, max_seq_len=128,
            hidden=args.hidden, num_heads=args.heads,
            num_layers=args.layers, ff_dim=4 * args.hidden)
        with DisaggCluster(d_ff_tcp) as d_cl:
            d_tcp = d_cl.generate_pipelined(
                d_prompts, d_new, temperature=d_temps, top_k=d_tks,
                sample_seed=args.seed)
            assert d_tcp == d_ref, (
                "--transport tcp disagg diverged from the in-process "
                "handoff")
            tcp_stats = dict(d_cl._receiver.stats)
            assert tcp_stats["wire_errors"] == 0
            assert tcp_stats["accepted"] > 0

        gates.append(
            f"fabric_wall_goodput_gain={wall_gain:.2f}x (thr "
            f"{res_t['goodput_per_s']:.1f}/s vs sgl "
            f"{res_s['goodput_per_s']:.1f}/s), wall==virtual, "
            f"pipelined+tcp==inproc")

        records.append({
            "metric": "serve_fabric_wall_goodput_gain",
            "value": round(wall_gain, 2),
            "unit": "x",
            "extra": {
                "platform": jax.default_backend(),
                "requests": f_reqs,
                "replicas": f_replicas,
                "dwell_ms": round(f_dwell * 1e3, 3),
                "time_scale": f_scale,
                "priced_step_ms": round(price * 1e3, 6),
                "wall_slo_ttft_ms": round(f_ttft * 1e3, 3),
                "wall_slo_tpot_ms": round(f_tpot * 1e3, 3),
                "goodput_wall_threaded_per_s": round(
                    res_t["goodput_per_s"], 2),
                "goodput_wall_single_per_s": round(
                    res_s["goodput_per_s"], 2),
                "goodput_virtual_per_s": round(
                    res_v["goodput_per_s"], 2),
                "slo_attainment_wall_threaded": round(
                    res_t["slo_attainment"], 4),
                "slo_attainment_wall_single": round(
                    res_s["slo_attainment"], 4),
                "wall_makespan_ms_threaded": round(
                    res_t["makespan_s"] * 1e3, 1),
                "wall_makespan_ms_single": round(
                    res_s["makespan_s"] * 1e3, 1),
                "busy_wall_s_threaded": [
                    round(p["busy_wall_s"], 4)
                    for p in res_t["per_replica"]],
                "sampled_requests": sum(
                    1 for t in ftraffic if t.sampled),
                "wall_matches_virtual": True,
                "pipelined_matches_phased": True,
                "tcp_matches_inproc": True,
                "tcp_frames": tcp_stats["frames"],
                "tcp_accepted": tcp_stats["accepted"],
                "tcp_wire_errors": tcp_stats["wire_errors"],
                "zero_recompiles": True,
                "pages_reclaimed": True,
            },
        })

    if args.workload in ("all", "spill"):
        # ---- workload 10: hierarchical host-tier prefix cache A/B
        # (tools/ci.sh step 1r, docs/serving.md "Hierarchical prefix
        # cache"). Long tenant preambles that can never ALL stay HBM-
        # resident (6 tenants x 24 prefix pages vs 40-page pools — one
        # running sequence plus churn always evicts the parked chain
        # head, so a repeat finds nothing matchable in HBM) serve the
        # same seeded traffic on a 2-replica affinity pool
        # three ways: host tier armed (pages evicted under pressure
        # spill their bytes to the SHARED host store and reload
        # through the existing fixed-shape import scatter when the
        # priced DMA beats recompute), plain eviction (identity
        # dropped, prefix recomputed — today's behavior), and
        # rung-3-style no-match (prefix matching off, the degradation
        # ladder's worst case). The reload DMA is priced by
        # TPUMachineModel.host_transfer and rides the SAME virtual
        # clock the steps do (StepEvents.host_reload_s), so the
        # goodput comparison is honest about the transfer cost.
        # Gates (smoke): host tier >= 1.3x goodput-under-SLO over
        # BOTH baselines, every completed request token-identical to
        # one reference engine, zero recompiles after warmup (spill/
        # reload reuse the export/import handoff programs), and
        # spills + priced reload decisions actually happened.
        from flexflow_tpu.serve.router import ReplicaPool
        from flexflow_tpu.serve.traffic import TrafficSpec, make_traffic
        from flexflow_tpu.utils.profiling import router_report

        s_ps = 8
        s_cfg = FFConfig(
            batch_size=1, kv_page_size=s_ps, kv_num_pages=1 + 40,
            serve_max_seqs=2, serve_prefill_budget=s_ps,
            serve_spec_decode=False)
        s_ff = build_transformer_lm(
            s_cfg, vocab_size=args.vocab, max_seq_len=256,
            hidden=args.hidden, num_heads=args.heads,
            num_layers=args.layers, ff_dim=4 * args.hidden)
        s_reqs = max(48, args.requests)
        s_replicas = 2

        def spill_pool(**over):
            return ReplicaPool(
                s_ff, s_replicas, policy="affinity",
                config=dataclasses.replace(s_cfg, **over))

        pool_h = spill_pool(host_tier_mb=8.0)
        assert pool_h.host_tier is not None, (
            "--host-tier-mb did not arm the pool's shared store")
        price = pool_h.price_probe(64)
        # the SLO sits BETWEEN the two repeat paths: a host reload
        # (one priced DMA event + the unshared tail, ~10-12 steps of
        # virtual time) lands inside 15x the probed step price, while
        # recomputing a 24-page preamble (24+ budget-limited prefill
        # steps) cannot — so attainment measures exactly what the
        # tier changes. Arrivals at 0.06/price keep the pool busy
        # without a standing queue: queueing delay is common-mode
        # across the arms and would otherwise wash the gap out.
        slo_ttft_s = 15.0 * price
        slo_tpot_s = 8.0 * price
        sspec = TrafficSpec(
            requests=s_reqs, seed=args.seed + 4, arrival="poisson",
            rate_rps=0.06 / price, tenants=6, prefix_tokens=192,
            tail_mean=5.0, output_mean=5.0, max_prompt=208,
            max_new_cap=8, cancel_frac=0.0, sample_frac=0.25,
            top_k=4, vocab=args.vocab)
        straffic = make_traffic(sspec)

        res_h = pool_h.run(straffic, slo_ttft_s=slo_ttft_s,
                           slo_tpot_s=slo_tpot_s,
                           sample_seed=args.seed)
        print(router_report(res_h, pool_h.metrics), file=sys.stderr)
        pool_h.assert_zero_recompiles()
        pool_h.check_drained()
        host = res_h["host_tier"] or {}

        # per-request priced decisions (the explain_request surface):
        # every decision carries both sides of the price, and at
        # least one chunk chose the DMA over recompute
        priced = [getattr(pool_h._req_refs[sid], "host_reload", None)
                  for sid in pool_h._req_refs]
        priced = [d for d in priced if d]
        for d in priced:
            assert d["dma_s"] >= 0.0 and d["recompute_s"] >= 0.0 \
                and d["chose"] in ("reload", "recompute",
                                   "store_miss"), d
            if d["chose"] == "recompute":
                assert d["dma_s"] >= d["recompute_s"], d

        pool_e = spill_pool(host_tier_mb=0.0)
        res_e = pool_e.run(straffic, slo_ttft_s=slo_ttft_s,
                           slo_tpot_s=slo_tpot_s,
                           sample_seed=args.seed)
        pool_e.assert_zero_recompiles()
        pool_e.check_drained()

        pool_n = spill_pool(serve_prefix_cache=False)
        res_n = pool_n.run(straffic, slo_ttft_s=slo_ttft_s,
                           slo_tpot_s=slo_tpot_s,
                           sample_seed=args.seed)
        pool_n.assert_zero_recompiles()
        pool_n.check_drained()

        # token identity: spilling a page to host RAM and importing
        # it back must never change a single emitted token, in any
        # arm — completed requests identical to ONE reference engine
        # serving the same stream ids, aborted ones a prefix
        ref_eng = ServeEngine(s_ff, spec_tokens=0)
        ref_eng.warmup()
        ref = ref_eng.generate(
            [t.prompt for t in straffic],
            [t.max_new for t in straffic],
            temperature=[t.temperature for t in straffic],
            top_k=[t.top_k for t in straffic],
            sample_seed=args.seed,
            stream_ids=[t.stream_id for t in straffic])
        for arm, res in (("host_tier", res_h), ("evict", res_e),
                         ("no_match", res_n)):
            for rec, r in zip(res["requests"], ref):
                if rec["outcome"] == "completed":
                    assert rec["tokens"] == r, (
                        f"{arm} stream {rec['stream_id']} diverged "
                        f"from the single-engine reference")
                else:
                    assert rec["tokens"] == r[:len(rec["tokens"])], (
                        f"{arm} aborted stream {rec['stream_id']} is "
                        f"not a reference prefix")

        # structural gates: the tier must actually have been
        # exercised — pressure spilled pages, and at least one
        # admission priced the DMA cheaper and reloaded
        for ok, msg in (
                (host.get("spills", 0) > 0,
                 "the host tier never spilled — the pool is not "
                 "under pressure"),
                (host.get("reload_pages", 0) > 0,
                 "no page was ever reloaded from the host tier"),
                (any(d["chose"] == "reload" for d in priced),
                 "no admission ever priced the reload cheaper than "
                 "recompute")):
            if not ok:
                assert not args.smoke, msg
                print(f"WARNING: {msg}", file=sys.stderr)

        gain_e = (res_h["goodput_per_s"]
                  / max(res_e["goodput_per_s"], 1e-12))
        gain_n = (res_h["goodput_per_s"]
                  / max(res_n["goodput_per_s"], 1e-12))
        gain = min(gain_e, gain_n)
        if gain < 1.3:
            msg = (f"host tier only {gain:.2f}x goodput-under-SLO "
                   f"(vs eviction {gain_e:.2f}x, vs no-match "
                   f"{gain_n:.2f}x; want >= 1.3x over both)")
            assert not args.smoke, msg
            print(f"WARNING: {msg}", file=sys.stderr)

        gates.append(
            f"host_tier_goodput={gain:.2f}x>=1.3x (evict "
            f"{gain_e:.2f}x, no-match {gain_n:.2f}x), "
            f"{host.get('spills', 0)} spills / "
            f"{host.get('reload_pages', 0)} reloaded pages, exact, "
            f"0 recompiles")

        records.append({
            "metric": "serve_host_tier_goodput_gain",
            "value": round(gain, 2),
            "unit": "x",
            "extra": {
                "platform": jax.default_backend(),
                "requests": s_reqs,
                "replicas": s_replicas,
                "tenants": sspec.tenants,
                "prefix_tokens": sspec.prefix_tokens,
                "hbm_pages_per_replica": s_cfg.kv_num_pages - 1,
                "host_tier_mb": 8.0,
                "priced_step_ms": round(price * 1e3, 6),
                "goodput_host_tier_per_s": round(
                    res_h["goodput_per_s"], 2),
                "goodput_evict_per_s": round(
                    res_e["goodput_per_s"], 2),
                "goodput_no_match_per_s": round(
                    res_n["goodput_per_s"], 2),
                "gain_vs_evict": round(gain_e, 2),
                "gain_vs_no_match": round(gain_n, 2),
                "slo_attainment_host_tier": round(
                    res_h["slo_attainment"], 4),
                "slo_attainment_evict": round(
                    res_e["slo_attainment"], 4),
                "slo_attainment_no_match": round(
                    res_n["slo_attainment"], 4),
                "host_spills": host.get("spills", 0),
                "host_reload_pages": host.get("reload_pages", 0),
                "host_recompute_chosen": host.get(
                    "recompute_chosen", 0),
                "host_evictions": host.get("evictions", 0),
                "host_reload_priced_ms": round(
                    host.get("reload_priced_s", 0.0) * 1e3, 4),
                "router_host_hits": res_h["routing"].get(
                    "host_hits", 0),
                "priced_decisions": len(priced),
                "outputs_match_reference": True,
                "zero_recompiles": True,
                "pages_reclaimed": True,
                "compile_counts": pool_h.compile_counts(),
            },
        })
        pool_h.close()
        pool_e.close()
        pool_n.close()

    if args.workload in ("all", "telemetry"):
        # ---- workload 6: telemetry on/off A/B (tools/ci.sh step 1k).
        # The observability contract (docs/observability.md): a
        # telemetry-on engine must produce bit-identical tokens with
        # zero recompiles at <= 3% wall overhead (all recording is
        # host-side — min of paired order-alternating on/off block
        # ratios, hard-gated under --smoke), the exported Chrome
        # trace must load with
        # well-formed per-request/per-step tracks, the Prometheus text
        # must parse, the metrics snapshot must carry the required
        # latency/robustness keys, and the drift calibrator must have
        # priced every serve regime it measured.
        import re
        from flexflow_tpu.utils.telemetry import Telemetry
        t_new = max(16, min(args.max_new, args.max_seq_len - 24))
        t_hi = args.max_seq_len - t_new
        tprompts = [list(rng.randint(1, args.vocab,
                                     size=rng.randint(4, t_hi + 1)))
                    for _ in range(args.requests)]
        eng_off = ServeEngine(ff)
        cnt_off = eng_off.warmup()
        tel = Telemetry()
        eng_on = ServeEngine(ff, telemetry=tel)
        cnt_on = eng_on.warmup()
        # Overhead statistic: the MINIMUM of paired on/off BLOCK
        # ratios — each block times GENS_PER_BLOCK back-to-back
        # generates per arm, adjacent in time and order-alternating.
        # Rationale: per-run jitter on a shared 2-core CI host is
        # +-10% at this ~200ms scale (measured), an order of magnitude
        # above the ~0.5% true recording cost, so no median/mean of
        # pair ratios resolves a 3% gate reliably. A REGRESSION in
        # recording cost shifts EVERY block ratio up uniformly, so the
        # cleanest-block minimum still detects it — while a one-sided
        # noise spike (scheduler, page cache) can no longer flap the
        # gate. The blocks average jitter internally; the min bounds
        # the intrinsic overhead from above under the least
        # interference observed (the repo's best-of-N convention for
        # this host, cf. search_bench). Block 0 also absorbs the
        # on-arm's one-time per-ctx-bucket drift predictions.
        GENS_PER_BLOCK = 3
        blocks = 5
        best_off = best_on = float("inf")
        ratios = []
        out_on = out_off = None
        for i in range(blocks):
            arms = ("off", "on") if i % 2 == 0 else ("on", "off")
            d = {}
            for arm in arms:
                t0 = time.perf_counter()
                for _ in range(GENS_PER_BLOCK):
                    if arm == "off":
                        out_off = eng_off.generate(tprompts, t_new)
                    else:
                        out_on = eng_on.generate(tprompts, t_new)
                d[arm] = time.perf_counter() - t0
            best_off = min(best_off, d["off"] / GENS_PER_BLOCK)
            best_on = min(best_on, d["on"] / GENS_PER_BLOCK)
            ratios.append(d["on"] / d["off"])
        assert out_on == out_off, (
            "telemetry-on outputs diverged from telemetry-off — "
            "recording must be pure observation")
        assert eng_on.compile_counts() == cnt_on and \
            eng_off.compile_counts() == cnt_off, (
                f"telemetry A/B recompiled: {cnt_on} -> "
                f"{eng_on.compile_counts()}")
        overhead = min(ratios)

        # metrics snapshot: the keys the router/autoscaler and the
        # perf trajectory depend on must all be present
        snap = tel.metrics_snapshot()
        met = snap["metrics"]
        for key in ("serve_tokens_generated_total",
                    "serve_engine_steps_total",
                    "serve_decode_steps_total",
                    "serve_prompt_tokens_total",
                    "serve_prefill_tokens_computed_total",
                    "serve_prefix_hit_tokens_total",
                    "serve_preemptions_total", "serve_retries_total",
                    'serve_requests_total{outcome="completed"}',
                    'serve_rung_steps_total{rung="0"}'):
            assert key in met["counters"], f"missing counter {key}"
        for key in ("serve_tokens_per_sec", "serve_pool_occupancy_peak",
                    "serve_prefix_hit_rate", "serve_spec_acceptance"):
            assert key in met["gauges"], f"missing gauge {key}"
        for key in ("serve_ttft_seconds", "serve_tpot_seconds",
                    "serve_request_latency_seconds"):
            assert key in met["histograms"], f"missing histogram {key}"

        # Prometheus text parses line by line
        line_re = re.compile(
            r'^(# (TYPE|HELP) [a-zA-Z_:][a-zA-Z0-9_:]* ?.*'
            r'|[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9eE.+-]+'
            r'|(nan|inf))$')
        for line in tel.to_prometheus().splitlines():
            if line:
                assert line_re.match(line), (
                    f"unparseable Prometheus line: {line!r}")

        # drift: every measured serve regime priced, ratios computed
        drift = snap["drift"]
        assert drift.get("serve"), "no serve drift regimes recorded"
        for reg, d in drift["serve"].items():
            assert d["count"] > 0 and d["predicted_ms_per_step"] > 0 \
                and d["measured_ms_per_step"] > 0, (reg, d)

        # Chrome trace: loads, well-formed, request + step tracks
        trace_path = (args.trace_out
                      or "/tmp/flexflow_tpu_serve_trace.json")
        tel.export_chrome_trace(trace_path)
        with open(trace_path) as f:
            doc = json.load(f)
        evs = doc["traceEvents"]
        assert evs, "empty trace"
        for ev in evs:
            assert ev["ph"] in ("X", "i", "M", "C", "b", "e"), ev
            assert isinstance(ev["pid"], int) \
                and isinstance(ev["tid"], int), ev
            if ev["ph"] != "M":
                assert isinstance(ev["ts"], (int, float)) \
                    and ev["ts"] >= 0, ev
            if ev["ph"] == "X":
                assert isinstance(ev["dur"], (int, float)) \
                    and ev["dur"] >= 0, ev
        threads = {ev["args"]["name"] for ev in evs
                   if ev["ph"] == "M" and ev["name"] == "thread_name"}
        assert "engine" in threads and any(
            t.startswith("slot ") for t in threads), threads

        if overhead > 1.03:
            msg = (f"telemetry overhead {overhead:.4f}x > 1.03x "
                   f"(min paired block ratio, {blocks} blocks of "
                   f"{GENS_PER_BLOCK}; best on {best_on*1e3:.1f} ms "
                   f"vs off {best_off*1e3:.1f} ms per generate; "
                   f"ratios {[round(r, 3) for r in sorted(ratios)]})")
            assert not args.smoke, msg
            print(f"WARNING: {msg}", file=sys.stderr)
        gates.append(f"telemetry_overhead={overhead:.4f}x<=1.03x "
                     f"trace+metrics+drift valid")
        print(tel.drift_report(), file=sys.stderr)

        records.append({
            "metric": "serve_telemetry_overhead",
            "value": round(overhead, 4),
            "unit": "x",
            "extra": {
                "platform": jax.default_backend(),
                "requests": args.requests,
                "max_new_tokens": t_new,
                "blocks": blocks,
                "gens_per_block": GENS_PER_BLOCK,
                "paired_block_ratios": [round(r, 4) for r in ratios],
                "wall_ms_off": round(best_off * 1e3, 3),
                "wall_ms_on": round(best_on * 1e3, 3),
                "outputs_identical": True,
                "compile_counts": eng_on.compile_counts(),
                "trace_path": trace_path,
                "trace_events": len(evs),
                "events_buffered": snap["events_buffered"],
                "ttft_ms_p50": round(tel.metrics.quantile(
                    "serve_ttft_seconds", 50) * 1e3, 4),
                "ttft_ms_p99": round(tel.metrics.quantile(
                    "serve_ttft_seconds", 99) * 1e3, 4),
                "tpot_ms_p50": round(tel.metrics.quantile(
                    "serve_tpot_seconds", 50) * 1e3, 4),
                "tpot_ms_p99": round(tel.metrics.quantile(
                    "serve_tpot_seconds", 99) * 1e3, 4),
                "drift_ratio_by_regime": {
                    reg: round(d["ratio"], 2)
                    for reg, d in drift["serve"].items()},
            },
        })

    # ---- workload: batched LoRA pool vs sequential weight swap ------
    if args.workload in ("all", "lora"):
        from flexflow_tpu.serve.adapters import (
            make_tenant_adapters, merge_adapter_params)
        TENANTS = 4                       # adapters; tenant 0 = base
        lora_rank = 4
        lora_reqs = max(args.requests, 12)
        lora_new = args.max_new
        head_dim = args.hidden // args.heads

        def lora_cfg(rank):
            return FFConfig(
                batch_size=1, kv_page_size=args.page_size,
                kv_num_pages=1 + pages_per_seq * args.max_seqs,
                serve_max_seqs=args.max_seqs,
                serve_prefill_budget=max(args.page_size,
                                         args.max_seq_len // 2),
                adapter_rank=rank)

        def lora_engine(rank):
            m = build_transformer_lm(
                lora_cfg(rank), vocab_size=args.vocab,
                max_seq_len=args.max_seq_len, hidden=args.hidden,
                num_heads=args.heads, num_layers=args.layers,
                ff_dim=4 * args.hidden)
            # speculation off in both arms: the A/B measures tenant
            # batching, and drafts would skew the step counts
            return ServeEngine(m, spec_tokens=0)

        adapters = make_tenant_adapters(
            num_layers=args.layers, hidden=args.hidden,
            num_heads=args.heads, head_dim=head_dim,
            ff_dim=4 * args.hidden, rank=lora_rank, tenants=TENANTS,
            seed=args.seed + 5)
        # Zipf-skewed tenant mix over 0..TENANTS (0 = base lanes), the
        # traffic-harness shape (serve/traffic.py): a few tenants
        # dominate, the tail churns the pool
        w = np.array([1.0 / (t + 1) ** 1.1 for t in range(TENANTS + 1)])
        w /= w.sum()
        tenant_mix = [int(rng.choice(TENANTS + 1, p=w))
                      for _ in range(lora_reqs)]
        if len(set(tenant_mix) - {0}) < 3:   # the gate needs >= 3
            tenant_mix[:3] = [1, 2, 3]       # adapters in one batch
        prompt_cap = max(9, (args.max_seq_len - lora_new) // 2)
        lora_prompts = [list(rng.randint(
            1, args.vocab, size=rng.randint(8, prompt_cap)))
            for _ in range(lora_reqs)]

        # arm A: ONE engine, every tenant batched through the adapter
        # pool in the one mixed program
        eng_a = lora_engine(lora_rank)
        counts_a = eng_a.warmup()
        for t, (wts, sc) in adapters.items():
            eng_a.register_adapter(t, wts, scale=sc)
        t0 = time.perf_counter()
        out_a = eng_a.generate(lora_prompts, lora_new,
                               tenant_ids=tenant_mix)
        wall_a = time.perf_counter() - t0
        st_a = eng_a.last_stats
        print(serve_report(st_a), file=sys.stderr)
        assert eng_a.compile_counts() == counts_a, (
            f"lora batched arm recompiled: "
            f"{counts_a} -> {eng_a.compile_counts()}")
        eng_a.cache.check_invariants()
        eng_a.adapters.check_invariants()

        # arm B: a weight-swap server — serve tenants SEQUENTIALLY,
        # merging each tenant's delta into the weights (same shapes,
        # so the swap itself never recompiles) and flushing the
        # prefix cache between tenants (unsalted tenant-0 chains
        # would otherwise serve one tenant another's pages)
        eng_b = lora_engine(0)
        counts_b = eng_b.warmup()
        base_params = eng_b.params
        merged = {0: base_params}
        for t, (wts, sc) in adapters.items():
            merged[t] = merge_adapter_params(base_params, wts, sc)
        out_b = [None] * lora_reqs
        steps_b = 0
        wall_b = 0.0
        for t in sorted(set(tenant_mix)):
            idxs = [i for i, ti in enumerate(tenant_mix) if ti == t]
            eng_b.params = eng_b._step_params = merged[t]
            eng_b.cache.clear_prefix()
            t0 = time.perf_counter()
            group = eng_b.generate([lora_prompts[i] for i in idxs],
                                   lora_new)
            wall_b += time.perf_counter() - t0
            steps_b += eng_b.last_stats["steps"]
            for i, o in zip(idxs, group):
                out_b[i] = o
        eng_b.params = eng_b._step_params = base_params
        assert eng_b.compile_counts() == counts_b, (
            f"lora swap arm recompiled: "
            f"{counts_b} -> {eng_b.compile_counts()}")

        # exactness: both arms equal the per-tenant merged-weight
        # references (the swap arm IS the merged server, so arm A ==
        # arm B is the tenant-isolation gate)
        assert out_a == out_b, (
            "batched adapter serving diverged from the weight-swap "
            "server")
        for i in (0, 1, 2, lora_reqs - 1):
            eng_b.params = merged[tenant_mix[i]]
            ref = eng_b.generate_reference([lora_prompts[i]],
                                           [lora_new])[0]
            assert out_a[i] == ref, (
                f"request {i} (tenant {tenant_mix[i]}) diverged from "
                f"its merged-weight reference")
        eng_b.params = base_params

        steps_a = st_a["steps"]
        gain = steps_b / max(steps_a, 1)
        wall_gain = wall_b / max(wall_a, 1e-9)
        if gain < 1.5:
            msg = (f"lora goodput gain {gain:.2f}x < 1.5x "
                   f"(batched {steps_a} steps vs swap {steps_b})")
            assert not args.smoke, msg
            print(f"WARNING: {msg}", file=sys.stderr)
        gates.append(f"lora_goodput={gain:.2f}x>=1.5x exact "
                     f"0 recompiles")

        pool = st_a["adapter_pool"]
        records.append({
            "metric": "serve_lora_goodput_gain",
            "value": round(gain, 2),
            "unit": "x",
            "extra": {
                "platform": jax.default_backend(),
                "requests": lora_reqs,
                "max_new_tokens": lora_new,
                "tenants": TENANTS,
                "adapter_rank": lora_rank,
                "adapter_slots": pool["usable_slots"],
                "steps_batched": steps_a,
                "steps_swap": steps_b,
                "wall_gain": round(wall_gain, 2),
                "wall_ms_batched": round(wall_a * 1e3, 1),
                "wall_ms_swap": round(wall_b * 1e3, 1),
                "adapter_loads": pool["loads"],
                "adapter_hits": pool["hits"],
                "adapter_evictions": pool["evictions"],
                "outputs_identical": True,
                "compile_counts": eng_a.compile_counts(),
            },
        })

    # ---------------- workload: cold vs warm replica boot --------------
    if args.workload in ("all", "boot"):
        # A/B the tentpole claim of the program registry
        # (core/programs.py): an engine whose --program-cache-dir holds
        # an AOT executable snapshot for its fingerprint must reach
        # first-token-ready >= 2x faster than a cold one, compile
        # NOTHING (compile_counts() all zero, the warm-boot contract),
        # and produce token-identical greedy output. JAX's persistent
        # compile cache is off for this workload: a "cold" arm that
        # hits it measures a disk read, not a compile. The snapshot
        # dir is deliberately fresh (a temporary name is right for the
        # *.ffprog store of an A/B, which no later run should find).
        import glob
        import shutil
        import tempfile
        import warnings as _warnings

        jax.config.update("jax_enable_compilation_cache", False)

        boot_prompts = [list(rng.randint(1, args.vocab, size=12))
                        for _ in range(4)]
        boot_new = max(4, min(8, args.max_new))

        def _boot_arm(cache_dir):
            """(engine, seconds-to-ready, greedy outputs): construction
            + warmup is the time a scale-up waits before the replica
            can serve — the number the autoscaler's boot_s prices."""
            bcfg = dataclasses.replace(cfg,
                                       program_cache_dir=cache_dir)
            t0 = time.perf_counter()
            eng = ServeEngine(ff, config=bcfg)
            eng.warmup()
            ready_s = time.perf_counter() - t0
            out = eng.generate(boot_prompts, boot_new)
            return eng, ready_s, out

        eng_cold, cold_s, out_cold = _boot_arm(None)
        assert sum(eng_cold.compile_counts().values()) > 0, (
            "cold arm compiled nothing — the A/B is vacuous")

        boot_dir = tempfile.mkdtemp(prefix="ffprog_boot_")
        try:
            # populate: the first engine over this (fingerprint, dir)
            # compiles and writes the snapshot back (warmup's
            # read-through write-back)
            eng_pop, _, _ = _boot_arm(boot_dir)
            eng_pop.close()
            eng_warm, warm_s, out_warm = _boot_arm(boot_dir)
            warm_counts = eng_warm.compile_counts()
            assert sum(warm_counts.values()) == 0, (
                f"warm arm compiled: {warm_counts} (expected zero — "
                f"every program should deserialize from the snapshot)")
            assert out_warm == out_cold, (
                "warm-boot outputs diverged from the in-process cold "
                "engine (the snapshot must be bit-identical)")
            restored = int(eng_warm.boot_stats["restored"])
            assert restored > 0 and eng_warm.boot_stats["warm"], (
                f"warm arm restored nothing: {eng_warm.boot_stats}")
            speedup = cold_s / max(warm_s, 1e-9)
            if speedup < 2.0:
                msg = (f"warm-boot speedup {speedup:.2f}x < 2x "
                       f"(cold {cold_s:.2f}s vs warm {warm_s:.2f}s)")
                assert not args.smoke, msg
                print(f"WARNING: {msg}", file=sys.stderr)

            # stale-cache rejection: a corrupt/truncated store must
            # fall back to compile-with-warning, never crash (the
            # cost_cache.py corrupt-store discipline)
            (store,) = glob.glob(os.path.join(boot_dir, "*.ffprog"))
            with open(store, "wb") as f:
                f.write(b"not a program snapshot")
            with _warnings.catch_warnings(record=True) as wlog:
                _warnings.simplefilter("always")
                eng_bad, _, out_bad = _boot_arm(boot_dir)
            assert any("program cache" in str(w.message)
                       for w in wlog), (
                "corrupt store produced no fallback warning")
            assert sum(eng_bad.compile_counts().values()) > 0, (
                "corrupt store arm compiled nothing — fallback "
                "did not recompile")
            assert out_bad == out_cold, (
                "corrupt-store fallback diverged from the cold engine")
            eng_bad.close()
            eng_warm.close()
        finally:
            shutil.rmtree(boot_dir, ignore_errors=True)
            jax.config.update("jax_enable_compilation_cache", True)
        eng_cold.close()

        gates.append(f"boot_warm={speedup:.1f}x>=2x "
                     f"{restored} restored 0 warm compiles exact "
                     f"corrupt-fallback")
        records.append({
            "metric": "serve_boot_warm_speedup",
            "value": round(speedup, 2),
            "unit": "x",
            "extra": {
                "platform": jax.default_backend(),
                "cold_ready_s": round(cold_s, 3),
                "warm_ready_s": round(warm_s, 3),
                "programs_restored": restored,
                "cold_compile_s": round(
                    float(eng_cold.boot_stats["compile_s"]), 3),
                "warm_compile_counts": warm_counts,
                "outputs_identical": True,
                "corrupt_fallback": True,
            },
        })

    if args.workload in ("all", "mesh2d"):
        # ---- workload 11: 2-D serve-mesh placement A/B (tools/ci.sh
        # step 1t, docs/search.md "2-D serve mesh"). ONE search prices
        # tensor degree x replica count x HBM residency into goodput-
        # under-SLO, and a pool BOOTED from the searched (t, r) must
        # beat both degenerate allocations of the SAME device budget:
        # tp-only (t=N, r=1 — all silicon on latency, no capacity, so
        # arrivals queue past the TTFT SLO) and replicas-only (t=1,
        # r=N — the model does not FIT one device, so every virtual
        # step pays the reference 1ms/MB over-capacity penalty and
        # blows the TPOT SLO). The HBM squeeze is constructed: a
        # machine file pins capacity BETWEEN the t=2 and t=1 per-
        # device residency, so the search REJECTS t=1 up front (never
        # priced, recorded with its residency) while the measured
        # t=1 arm demonstrates what the rejection predicted. Tenants
        # share prefixes and the LoRA adapter pool is armed in every
        # arm. Gates (smoke): >= 1.3x goodput-under-SLO vs BOTH
        # baselines, t=1 infeasible (not a table row), every arm
        # token-identical to ONE reference engine (greedy AND
        # sampled), zero recompiles per replica after warmup.
        import tempfile

        from flexflow_tpu.search.cost_model import serve_device_bytes
        from flexflow_tpu.search.machine_model import \
            default_machine_model
        from flexflow_tpu.search.serve_place import (MeshTraffic,
                                                     mesh_cell_metrics,
                                                     optimize_serve_mesh,
                                                     price_mesh_step)
        from flexflow_tpu.serve.adapters import make_tenant_adapters
        from flexflow_tpu.serve.engine import probe_serve_arch
        from flexflow_tpu.serve.router import ReplicaPool
        from flexflow_tpu.serve.traffic import TrafficSpec, make_traffic
        from flexflow_tpu.utils.profiling import router_report

        if len(jax.devices()) < 4:
            print("mesh2d workload skipped: needs >= 4 devices "
                  f"(have {len(jax.devices())})", file=sys.stderr)
        else:
            m_devices = 4
            m_ps = 8
            m_hidden = max(64, args.hidden)
            m_rank = 4
            m_cfg = FFConfig(
                batch_size=1, kv_page_size=m_ps, kv_num_pages=1 + 40,
                serve_max_seqs=4, serve_prefill_budget=m_ps,
                serve_spec_decode=False, adapter_rank=m_rank)
            m_ff = build_transformer_lm(
                m_cfg, vocab_size=args.vocab, max_seq_len=128,
                hidden=m_hidden, num_heads=args.heads,
                num_layers=args.layers, ff_dim=4 * m_hidden)
            m_arch = probe_serve_arch(m_ff, m_cfg)
            # the squeeze, at the engine's WORST-case context so no
            # runtime ctx bucket can put the sharded arms over budget
            worst = dataclasses.replace(m_arch, context=128)
            m_b1 = serve_device_bytes(worst, 1)
            m_b2 = serve_device_bytes(worst, 2)
            hbm = m_b2 + 0.05 * (m_b1 - m_b2)
            mm_path = os.path.join(
                tempfile.mkdtemp(prefix="ffmesh_"), "machine.json")
            with open(mm_path, "w") as f:
                json.dump({"hbm_capacity": hbm}, f)
            m_cfg.machine_model_file = mm_path

            # the search's traffic model, scaled off ITS OWN step
            # price (the same simulate_serve_step the pool's virtual
            # clock uses): arrival 1.6x one sharded replica's priced
            # capacity, so every r=1 cell saturates (queueing blows
            # the TTFT SLO in the M/D/c term) and a multi-replica
            # cell is the only way to goodput
            m_mm = default_machine_model(machine_file=mm_path)
            d2, p2, x2 = price_mesh_step(m_arch, 2, m_mm)
            cap1 = mesh_cell_metrics(
                m_arch, 2, 1, d2, p2, x2,
                MeshTraffic(arrival_rps=1.0))["capacity_rps"]
            m_model_traffic = MeshTraffic(
                arrival_rps=1.6 * cap1, prefix_hit=0.5,
                requests_per_preamble=8.0,
                slo_ttft_s=60.0 * p2, slo_tpot_s=2.5 * x2)
            place = optimize_serve_mesh(
                m_arch, m_devices, config=m_cfg,
                traffic=m_model_traffic, seed=args.seed)
            assert [d["tensor"] for d in place.infeasible] == [1], (
                f"expected exactly t=1 HBM-rejected, got "
                f"{place.infeasible}")
            assert all(t != 1 for (t, _r) in place.table), (
                "a rejected degree leaked into the price table")
            assert place.replicas >= 2, (
                f"search kept one replica (t={place.tensor_parallel} "
                f"r={place.replicas}) — the saturation geometry is "
                f"broken")
            print(f"mesh2d searched placement: "
                  f"t={place.tensor_parallel} x r={place.replicas} "
                  f"goodput {place.goodput_per_s:.1f}/s "
                  f"(vs tp-only {place.goodput_gain_vs_tensor_only():.2f}x)",
                  file=sys.stderr)

            m_adapters = make_tenant_adapters(
                num_layers=args.layers, hidden=m_hidden,
                num_heads=args.heads,
                head_dim=m_hidden // args.heads,
                ff_dim=4 * m_hidden, rank=m_rank, tenants=3,
                seed=args.seed + 9)

            def m_pool(t, r):
                p = ReplicaPool(m_ff, r, policy="affinity",
                                engine_kwargs={"tensor_parallel": t})
                for ten, (w, sc) in sorted(m_adapters.items()):
                    p.register_adapter(ten, w, scale=sc)
                return p

            pool_mesh = m_pool(place.tensor_parallel, place.replicas)
            assert len(pool_mesh.replicas) == place.replicas
            assert all(r.engine.tp == place.tensor_parallel
                       for r in pool_mesh.replicas)
            # SLO targets and arrival rate as multiples of the
            # SEARCHED arm's priced step — identical across arms, so
            # the A/B measures the allocation, not the yardstick
            price = pool_mesh.price_probe(64)
            m_slo_ttft = 20.0 * price
            m_slo_tpot = 2.5 * price
            m_reqs = max(40, args.requests)
            m_spec = TrafficSpec(
                requests=m_reqs, seed=args.seed + 1,
                arrival="poisson", rate_rps=0.15 / price, tenants=4,
                prefix_tokens=48, tail_mean=4.0, output_mean=6.0,
                max_prompt=80, max_new_cap=10, sample_frac=0.25,
                top_k=4, vocab=args.vocab)
            m_traffic = make_traffic(m_spec)

            arm_shapes = {
                "searched": (place.tensor_parallel, place.replicas),
                "tp_only": (m_devices, 1),
                "replicas_only": (1, m_devices),
            }
            m_res = {}
            for arm, (t, r) in arm_shapes.items():
                p = pool_mesh if arm == "searched" else m_pool(t, r)
                m_res[arm] = p.run(m_traffic, slo_ttft_s=m_slo_ttft,
                                   slo_tpot_s=m_slo_tpot,
                                   sample_seed=args.seed)
                p.assert_zero_recompiles()
                p.check_drained()
                if arm == "searched":
                    print(router_report(m_res[arm], p.metrics),
                          file=sys.stderr)
                else:
                    p.close()

            # token identity vs ONE reference engine serving the same
            # stream ids with the same armed adapters: the allocation
            # must never change tokens (completed exact, aborted a
            # prefix) — in every arm, sharded and penalized alike
            ref_eng = ServeEngine(m_ff, spec_tokens=0)
            ref_eng.warmup()
            for ten, (w, sc) in sorted(m_adapters.items()):
                ref_eng.register_adapter(ten, w, scale=sc)
            ref = ref_eng.generate(
                [t.prompt for t in m_traffic],
                [t.max_new for t in m_traffic],
                temperature=[t.temperature for t in m_traffic],
                top_k=[t.top_k for t in m_traffic],
                sample_seed=args.seed,
                stream_ids=[t.stream_id for t in m_traffic],
                tenant_ids=[t.tenant for t in m_traffic])
            for arm, res in m_res.items():
                for rec, rtoks in zip(res["requests"], ref):
                    if rec["outcome"] == "completed":
                        assert rec["tokens"] == rtoks, (
                            f"{arm} stream {rec['stream_id']} "
                            f"diverged from the reference engine")
                    else:
                        assert rec["tokens"] == \
                            rtoks[:len(rec["tokens"])], (
                                f"{arm} aborted stream "
                                f"{rec['stream_id']} is not a "
                                f"reference prefix")
            assert any(rec["sampled"] and rec["outcome"] == "completed"
                       for rec in m_res["searched"]["requests"]), (
                "the exactness gate never saw a completed SAMPLED "
                "stream")

            g_tp = (m_res["searched"]["goodput_per_s"]
                    / max(m_res["tp_only"]["goodput_per_s"], 1e-9))
            g_rep = (m_res["searched"]["goodput_per_s"]
                     / max(m_res["replicas_only"]["goodput_per_s"],
                           1e-9))
            gain = min(g_tp, g_rep)
            if gain < 1.3:
                msg = (f"searched (t={place.tensor_parallel}, "
                       f"r={place.replicas}) only {gain:.2f}x the "
                       f"degenerate baselines (tp-only {g_tp:.2f}x, "
                       f"replicas-only {g_rep:.2f}x; want >= 1.3x "
                       f"both)")
                assert not args.smoke, msg
                print(f"WARNING: {msg}", file=sys.stderr)
            gates.append(
                f"mesh2d_goodput={gain:.2f}x>=1.3x "
                f"(t={place.tensor_parallel} r={place.replicas}: "
                f"{m_res['searched']['goodput_per_s']:.0f}/s vs "
                f"tp-only {m_res['tp_only']['goodput_per_s']:.0f}/s, "
                f"replicas-only "
                f"{m_res['replicas_only']['goodput_per_s']:.0f}/s) "
                f"t=1 HBM-rejected exact 0 recompiles")

            records.append({
                "metric": "serve_mesh2d_goodput_gain",
                "value": round(gain, 2),
                "unit": "x",
                "extra": {
                    "platform": jax.default_backend(),
                    "requests": m_reqs,
                    "devices": m_devices,
                    "searched_tensor": place.tensor_parallel,
                    "searched_replicas": place.replicas,
                    "searched_goodput_per_s": round(
                        m_res["searched"]["goodput_per_s"], 2),
                    "tp_only_goodput_per_s": round(
                        m_res["tp_only"]["goodput_per_s"], 2),
                    "replicas_only_goodput_per_s": round(
                        m_res["replicas_only"]["goodput_per_s"], 2),
                    "gain_vs_tp_only": round(g_tp, 2),
                    "gain_vs_replicas_only": round(g_rep, 2),
                    "slo_attainment_searched": round(
                        m_res["searched"]["slo_attainment"], 4),
                    "priced_step_ms": round(price * 1e3, 6),
                    "slo_ttft_steps": 20.0, "slo_tpot_steps": 2.5,
                    "hbm_capacity_bytes": round(hbm),
                    "device_bytes_t1": round(m_b1),
                    "device_bytes_t2": round(m_b2),
                    "infeasible_degrees": [
                        d["tensor"] for d in place.infeasible],
                    "model_goodput_per_s": round(
                        place.goodput_per_s, 2),
                    "model_gain_vs_tensor_only": round(
                        place.goodput_gain_vs_tensor_only(), 2),
                    "search_table_cells": len(place.table),
                    "adapter_rank": m_rank,
                    "tenants": m_spec.tenants,
                    "prefix_tokens": m_spec.prefix_tokens,
                    "outputs_match_reference": True,
                    "zero_recompiles": True,
                    "compile_counts": pool_mesh.compile_counts(),
                },
            })
            pool_mesh.close()

    print("\n".join(json.dumps(r) for r in records))
    if args.out:
        # merge-by-metric JSONL through the ONE shared writer
        # (tools/_bench_io.py, the format BENCH_search.json shares):
        # a partial --workload run refreshes ITS lines without
        # deleting the other workloads' records, tolerating
        # individually corrupt lines in the old artifact
        from _bench_io import write_records
        write_records(args.out, records)
    if args.smoke:
        print(f"serve smoke OK: {'; '.join(gates)}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
