#!/bin/bash
# Committed CI gate — the reference's .circleci/config.yml analog
# (build + pytest + multi-GPU script tests + accuracy tests per
# commit). Everything here runs on the virtual 8-device CPU platform,
# so it needs no hardware.
#
#   bash tools/ci.sh          # fast gate: default pytest profile
#                             #   (<~5 min) + multichip dryrun +
#                             #   3 example smokes
#   bash tools/ci.sh --full   # + the slow remainder (-m slow):
#                             #   example zoo, model smokes,
#                             #   multiprocess, pipelines (~35 min)
#
# Writes .scratch/ci_last_green (HEAD sha + UTC stamp + mode) on
# success.
set -u -o pipefail
cd "$(dirname "$0")/.."
FULL="${1:-}"
fail=0

echo "=== ci $(date -u +%FT%TZ) HEAD=$(git rev-parse --short HEAD) mode=${FULL:-fast} ==="

echo "--- 1. fast CPU suite (default profile: -m 'not slow')"
# --continue-on-collection-errors keeps one broken module from masking
# the rest of the suite, but a module that fails to COLLECT must still
# gate: pytest's "N errors" summary only appears for collection/setup
# errors, so grep the log and flip fail even when the run "passes".
python -m pytest tests/ -q --continue-on-collection-errors 2>&1 \
    | tee /tmp/ci_tier1.log || fail=1
if grep -qaE '^ERROR |^[0-9]+ errors?|[0-9]+ errors? in ' /tmp/ci_tier1.log
then
  echo "!!! pytest collection errors (see above) — failing the gate"
  fail=1
fi

echo "--- 1c. search-bench smoke (delta-sim speedup + equivalence gate)"
# fails if the delta path's speedup over full simulation is < 2x or if
# delta/full makespans diverge (tools/search_bench.py --smoke)
env JAX_PLATFORMS=cpu python tools/search_bench.py --smoke || fail=1

echo "--- 1d. serve-bench smoke (zero recompiles + prefix-cache gate)"
# fails if serving compiles anything after warmup, if prefix-cached
# outputs diverge from generate_reference, or if the shared-prefix
# workload's prefill-token reduction is < 2x (tools/serve_bench.py)
env JAX_PLATFORMS=cpu python tools/serve_bench.py --smoke --workload base \
    -o /tmp/ci_bench_serve.json || fail=1

echo "--- 1e. mixed-precision smoke (bf16 makespan + parity gate)"
# fails if the simulated bf16 step-makespan reduction on the TPU
# machine model is < 1.3x (transformer or DLRM), if the bf16 loss
# curve drifts from f32 past tolerance, or if the cost-cache
# fingerprint fails to separate precision policies (tools/mp_bench.py)
env JAX_PLATFORMS=cpu python tools/mp_bench.py --smoke \
    -o /tmp/ci_bench_mp.json || fail=1

echo "--- 1f. speculative-decode smoke (step-reduction + exactness gate)"
# fails if the repetitive-text workload's decode-step reduction is
# < 1.5x, if speculative (or baseline) outputs diverge from
# generate_reference, or if anything compiles after warmup
# (tools/serve_bench.py --workload spec)
env JAX_PLATFORMS=cpu python tools/serve_bench.py --smoke --workload spec \
    -o /tmp/ci_bench_serve_spec.json || fail=1

echo "--- 1g. chaos smoke (fault-injected serving gate)"
# the base workload under a SEEDED fault spec (transient dispatch
# errors + page-pool exhaustion) plus a cancel/deadline storm: fails
# unless every surviving request is token-identical to
# generate_reference, PagedKVCache.check_invariants holds after every
# step, every page is reclaimed, and nothing compiles after warmup
# (docs/robustness.md)
env JAX_PLATFORMS=cpu python tools/serve_bench.py --smoke --workload base \
    --fault-spec 'serve.mixed:transient@3,6,11;serve.page_pressure:exhaust:0.9@4-9' \
    -o /tmp/ci_bench_serve_chaos.json || fail=1

echo "--- 1h. train-bench smoke (async runtime >= 1.10x + exactness gate)"
# fails if the overlapped training runtime (grouped dispatch + depth-2
# window + bucketed grad sync) is < 1.10x faster per step than the
# synchronous path on dlrm OR transformer, if the loss trajectories are
# not bit-identical, if anything compiles after warmup, or if the
# simulator prices overlapped sync slower than serialized
# (tools/train_bench.py)
env JAX_PLATFORMS=cpu python tools/train_bench.py --smoke \
    -o /tmp/ci_bench_train.json || fail=1

echo "--- 1i. kv-quantization smoke (int8 page capacity + parity gate)"
# int8 KV pages vs f32 at an EQUAL pool byte budget: fails unless the
# effective page capacity is >= 1.9x, the same requests run at higher
# decode concurrency in fewer engine steps, int8 greedy outputs hold
# token parity with the no-cache reference up to tie-margin flips
# (and are chunk-boundary invariant), and nothing compiles after
# warmup. The f32 arm also re-gates kernel-v2 bit-exactness + zero
# recompiles (tools/serve_bench.py --workload kv, docs/serving.md)
env JAX_PLATFORMS=cpu python tools/serve_bench.py --smoke --workload kv \
    -o /tmp/ci_bench_serve_kv.json || fail=1

echo "--- 1j. sharded-serving smoke (tensor-parallel parity + sim speedup gate)"
# the SAME model served single-device vs head-sharded over a forced
# 4-device host mesh: fails unless greedy outputs are token-identical,
# nothing compiles after warmup, the per-device KV pool and dispatched
# FLOPs shrink ~4x, and the placement search's simulated v5e
# decode-step latency at t=4 is >= 1.5x better than t=1 on the
# Gemma-31B-class serving arch (tools/serve_bench.py --workload shard,
# docs/serving.md "Sharded serving")
env JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=4" \
    python tools/serve_bench.py --smoke --workload shard \
    -o /tmp/ci_bench_serve_shard.json || fail=1

echo "--- 1k. telemetry smoke (trace export + metrics + <=3% overhead gate)"
# telemetry-on serving must be token-identical to telemetry-off with
# zero recompiles at <= 3% wall overhead (min paired on/off block
# ratio, order-alternating interleave); the
# exported Chrome trace must load with well-formed per-request/per-step
# tracks (every ts/dur/pid/tid checked), the Prometheus text must
# parse, the metrics snapshot must carry the required TTFT/TPOT/pool/
# robustness keys, and drift_report must price every measured serve
# regime (tools/serve_bench.py --workload telemetry,
# docs/observability.md)
env JAX_PLATFORMS=cpu python tools/serve_bench.py --smoke --workload telemetry \
    --trace-out /tmp/ci_serve_trace.json \
    -o /tmp/ci_bench_serve_telemetry.json || fail=1

echo "--- 1l. observability smoke (simulated-trace + search-trace + ledger + endpoint gate)"
# explainable-search tentpole (tools/explain.py --smoke,
# docs/observability.md): the exported simulated-schedule trace must be
# Perfetto-schema-valid with its end time bit-equal to the simulator's
# returned makespan (train + serve); search tracing on vs off must be
# bit-identical at the same seed with the search_trace record present
# in BENCH_search.json; the HBM memory ledger must match the live
# device buffers within 5% on a real ServeEngine (explain_placement
# component sums exact); and the --metrics-port endpoint must serve a
# parseable /metrics page + /healthz, going down cleanly on close().
# The 1k telemetry-overhead gate above is unchanged.
env JAX_PLATFORMS=cpu python tools/explain.py --smoke || fail=1

echo "--- 1m. disaggregated-serving smoke (TPOT-p99 + handoff exactness gate)"
# unified vs prefill/decode-disaggregated serving under mixed
# heavy-prefill + steady-decode traffic at equal device count: fails
# unless the cluster's outputs are token-identical to the unified
# engine (pages crossed the handoff link), nothing compiles after
# DisaggCluster.warmup() on either role, and the TPOT-p99 reduction —
# measured on this host or simulated by the ratio search (priced
# page-transfer link, Gemma-31B-class arch on 16 v5e chips) — is
# >= 1.3x (tools/serve_bench.py --workload disagg, docs/serving.md
# "Disaggregated serving")
env JAX_PLATFORMS=cpu python tools/serve_bench.py --smoke --workload disagg \
    -o /tmp/ci_bench_serve_disagg.json || fail=1

echo "--- 1n. multi-replica router smoke (goodput-under-SLO + exactness gate)"
# prefix-affinity routing vs round-robin over a 3-replica simulated
# cluster on a seeded multi-tenant prefix mix (Poisson arrivals,
# heavy-tailed lengths, cancels, seeded sampling; virtual time priced
# by the cost model): fails unless affinity's goodput-under-SLO is
# >= 1.3x round-robin's, every completed request is token-identical
# to a single replica serving the same stream ids, no replica
# compiles after its own warmup, every page reclaims after drain,
# and the telemetry-driven autoscaler's decisions replay identically
# across two runs with spans emitted (tools/serve_bench.py
# --workload router, docs/serving.md "Multi-replica routing")
env JAX_PLATFORMS=cpu python tools/serve_bench.py --smoke --workload router \
    -o /tmp/ci_bench_serve_router.json || fail=1

echo "--- 1o. SLO burn-rate + flight-recorder smoke (request-observability gate)"
# the request-observability tentpole (docs/observability.md): the SLO
# burn-rate monitor must fire AND clear on a deterministic outage
# history, replay bit-identically, and export parseable burn gauges
# (tools/slo_report.py --smoke, no jax — pure host python); the
# failure flight recorder must leave a loadable, schema-valid
# post-mortem bundle when a chaos-injected FATAL dispatch fault aborts
# a real engine mid-batch (plus deadline-storm and explicit triggers),
# with the engine still serving afterwards (tools/postmortem.py
# --smoke). The 1k <=1.03x telemetry-overhead gate is unchanged.
python tools/slo_report.py --smoke || fail=1
env JAX_PLATFORMS=cpu python tools/postmortem.py --smoke || fail=1

echo "--- 1p. multi-tenant LoRA smoke (batched-pool goodput + exactness gate)"
# batched multi-tenant adapter serving vs a sequential per-tenant
# weight-swap server on a Zipf tenant mix: fails unless the batched
# pool's goodput (mixed steps for the same token set) is >= 1.5x the
# swap server's, every stream is token-identical to its tenant's
# merged-weight reference, and nothing compiles after warmup on
# either arm — adapter loads are dispatches of the one scatter
# program, never recompiles (tools/serve_bench.py --workload lora,
# docs/serving.md "Multi-tenant adapters")
env JAX_PLATFORMS=cpu python tools/serve_bench.py --smoke --workload lora \
    -o /tmp/ci_bench_serve_lora.json || fail=1

echo "--- 1q. wall-clock fabric smoke (wall==virtual identity + concurrency gate)"
# the wall-clock twin of the serving tier: the same seeded traffic on
# the virtual clock vs the threaded and single-threaded wall clock —
# fails unless all three arms are token-identical at one seed
# (sampling keys on stream ids, never on the clock), the threaded
# wall goodput-under-SLO is >= 1.3x the single-threaded baseline
# (per-step device dwell overlapping across replica worker threads),
# and the disaggregated cluster's continuous-pipelined and
# --transport tcp (loopback socket PageShipment frames) arms match
# the phased in-process handoff token-for-token
# (tools/serve_bench.py --workload fabric, docs/serving.md
# "Wall-clock mode")
env JAX_PLATFORMS=cpu python tools/serve_bench.py --smoke --workload fabric \
    -o /tmp/ci_bench_serve_fabric.json || fail=1

echo "--- 1r. host-tier prefix-cache smoke (spill-vs-recompute goodput gate)"
# the hierarchical prefix-cache tier (serve/host_tier.py): on a
# working-set-larger-than-pool multi-tenant stream, pages evicted
# under HBM pressure spill their bytes to a shared host-RAM store and
# reload through the existing fixed-shape import scatter when the
# DMA priced by TPUMachineModel.host_transfer beats prefill recompute
# — fails unless the host-tier arm's goodput-under-SLO is >= 1.3x
# BOTH plain eviction and rung-3-style no-match degradation, every
# completed request is token-identical to a single reference engine,
# nothing compiles after warmup (spill/reload reuse the export/import
# handoff programs), and spills + priced reload decisions actually
# happened (tools/serve_bench.py --workload spill, docs/serving.md
# "Hierarchical prefix cache")
env JAX_PLATFORMS=cpu python tools/serve_bench.py --smoke --workload spill \
    -o /tmp/ci_bench_serve_spill.json || fail=1

echo "--- 1s. warm replica boot smoke (AOT program-cache gate)"
# the ProgramRegistry AOT compile cache (core/programs.py,
# --program-cache-dir): a cold engine compiles + snapshots its
# executables, and a second engine over the same program fingerprint
# must boot from the deserialized snapshot — fails unless
# time-to-first-token-ready drops >= 2x, the warm arm's
# compile_counts() report ZERO compiles (the registry counts exactly,
# so a hidden compile cannot pass), its greedy tokens equal the
# in-process cold engine's bit-for-bit, and a corrupted/truncated
# store falls back to compile-with-warning instead of crashing (the
# cost_cache.py corrupt-store discipline)
# (tools/serve_bench.py --workload boot, docs/performance.md
# "Warm boot")
env JAX_PLATFORMS=cpu python tools/serve_bench.py --smoke --workload boot \
    -o /tmp/ci_bench_serve_boot.json || fail=1

echo "--- 1t. 2-D serve-mesh placement smoke (search-vs-degenerate gate)"
# the 2-D placement search (search/serve_place.optimize_serve_mesh,
# docs/search.md "2-D serve mesh"): ONE walk prices tensor degree x
# replica count x HBM residency into goodput-under-SLO, and a pool
# booted from the searched (t, r) must beat BOTH degenerate
# allocations of the same 4-device budget — best tp-only (r=1,
# arrivals queue past the TTFT SLO) and best replicas-only (t=1, the
# model over-fills one device's HBM so every step pays the reference
# 1ms/MB penalty and blows TPOT; the search rejects t=1 up front,
# never pricing it) — by >= 1.3x, with shared-prefix tenants + the
# armed LoRA adapter pool, token identity vs one reference engine,
# and zero recompiles after warmup
# (tools/serve_bench.py --workload mesh2d)
env JAX_PLATFORMS=cpu python tools/serve_bench.py --smoke --workload mesh2d \
    -o /tmp/ci_bench_serve_mesh2d.json || fail=1

if [ "$FULL" = "--full" ]; then
  echo "--- 1b. slow remainder (-m slow)"
  python -m pytest tests/ -q -m slow --continue-on-collection-errors 2>&1 \
      | tee /tmp/ci_tier1_slow.log || fail=1
  if grep -qaE '^ERROR |^[0-9]+ errors?|[0-9]+ errors? in ' \
      /tmp/ci_tier1_slow.log
  then
    echo "!!! pytest collection errors (slow profile) — failing the gate"
    fail=1
  fi
fi

echo "--- 2. multichip dryrun (all parallel axes on 8 virtual devices)"
env XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    JAX_PLATFORMS=cpu python -c "
import jax
import __graft_entry__ as g
g.dryrun_multichip(8)
fn, args = g.entry(); jax.jit(fn)(*args)
print('entry() compile OK')" || fail=1

echo "--- 3. example smokes (native / frontend / keras)"
timeout 300 python -m flexflow_tpu --cpu-devices 2 \
    examples/python/native/alexnet.py -b 8 --samples 16 -e 1 \
    >/dev/null || fail=1
timeout 300 python -m flexflow_tpu --cpu-devices 2 \
    examples/python/pytorch/mnist_mlp_torch.py -e 1 \
    >/dev/null || fail=1
timeout 300 python -m flexflow_tpu --cpu-devices 2 \
    examples/python/keras/mnist_mlp.py -e 1 >/dev/null || fail=1
echo "example smokes rc=$fail"

if [ "$fail" -eq 0 ]; then
  mkdir -p .scratch
  echo "$(git rev-parse HEAD) $(date -u +%FT%TZ) mode=${FULL:-fast}" \
      > .scratch/ci_last_green
  echo "=== ci GREEN ==="
else
  echo "=== ci RED ==="
fi
exit "$fail"
