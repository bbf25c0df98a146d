"""Runtime configuration for the TPU-native FlexFlow rebuild.

Mirrors the knob surface of the reference `FFConfig` (reference:
include/config.h:98-154, parse_args src/runtime/model.cc:2258-2379) but
re-targeted at TPU execution: instead of Legion `-ll:*` resource flags the
machine is described by a `jax.sharding.Mesh` (see
:mod:`flexflow_tpu.parallel.mesh`).
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp


class CompMode:
    """Computation mode (reference: ffconst.h COMP_MODE_TRAINING/INFERENCE)."""

    TRAINING = "training"
    INFERENCE = "inference"


class ParameterSyncType:
    """Kept for API compatibility with the reference (ffconst.h:44-48).

    On TPU both modes lower to XLA collectives chosen by GSPMD; `PS` and
    `NCCL` differ only in how the reference moved gradients, which has no
    TPU analog (SURVEY.md section 7, hard part (e)).
    """

    NONE = "none"
    PS = "ps"
    NCCL = "nccl"


# KV-page storage formats the serve stack supports (--kv-dtype). The
# ONE allowlist: serve/kv_cache.py derives its byte accounting from it.
# "float8_e4m3" stores ml_dtypes' e4m3fn pages and reuses the int8
# per-row scale machinery verbatim (serve/kv_cache.kv_storage_dtype).
KV_DTYPES = ("float32", "bfloat16", "int8", "float8_e4m3")


@dataclasses.dataclass
class FFIterationConfig:
    """Per-iteration runtime config (reference: include/config.h:156-161).

    ``seq_length`` truncates sequence-bearing shapes (BatchMatmul /
    attention) for variable-length batches.
    """

    seq_length: int = -1

    def reset(self) -> None:
        self.seq_length = -1


def _int_or_auto(v) -> Union[int, str]:
    """--serve-replicas value parser: an explicit replica count, or
    'auto' to resolve the pool shape through the 2-D serve-mesh
    search (search/serve_place.optimize_serve_mesh)."""
    s = str(v).strip()
    return "auto" if s == "auto" else int(s)


@dataclasses.dataclass
class FFConfig:
    """All runtime knobs.

    Reference parity (include/config.h:98-154):
      batchSize -> batch_size, epochs -> epochs, iterations -> iterations,
      numNodes/workersPerNode -> described by the mesh,
      learningRate/weightDecay -> lr/weight_decay (consumed by optimizers),
      search_budget/search_alpha/search_overlap_backward_sync ->
        search_* (consumed by flexflow_tpu.search.mcmc),
      import_strategy_file/export_strategy_file -> strategy I/O,
      enable_sample_parallel/parameter_parallel/attribute_parallel ->
        search-space gates, plus the new TPU-first axes (sequence/expert/
        pipeline parallel) which the reference lacked (SURVEY.md 2.4).
    """

    batch_size: int = 64
    epochs: int = 1
    iterations: int = 1
    learning_rate: float = 0.01
    weight_decay: float = 0.0001
    seed: int = 0

    # numerics — the mixed-precision policy (core/precision.py):
    # `param_dtype` is the MASTER storage dtype of float parameters and
    # optimizer state (f32 by default — the loss-scaling-free bf16
    # recipe keeps f32 masters); `compute_dtype` is the dtype
    # params/activations are cast to INSIDE the jitted step (bf16 runs
    # the MXU at ~2x f32 rate and halves HBM/ICI bytes). Softmax/LSE,
    # losses, metrics, BN/LN statistics and reduction accumulators stay
    # f32 regardless (preferred_element_type — the flash-attention
    # convention). The strategy-search cost stack prices both dtypes
    # (search/machine_model.py, search/cost_model.py).
    compute_dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32

    # profiling / debugging
    profiling: bool = False
    log_instance_creation: bool = False
    # jax.profiler trace directory for utils/profiling.trace()
    # (TensorBoard-viewable XLA traces); None = <checkout>/.scratch/trace.
    # --trace-dir.
    trace_dir: Optional[str] = None

    # ---- telemetry (utils/telemetry.py, docs/observability.md) ----
    # structured event bus + metrics registry + simulator-drift
    # calibrator: per-request lifecycle spans in ServeEngine (queue
    # wait, prefill chunks, decode steps, preemption, speculation,
    # retries, degradation rungs, cancel/deadline) and per-step train
    # spans in fit (dispatch, fetch wait), with Chrome-trace and
    # Prometheus-style exporters. Host-side only: telemetry on vs off
    # is token-identical with zero recompiles at <= 3% step-time
    # overhead (ci.sh step 1k). --telemetry enables; --trace-out PATH
    # also enables and writes the Chrome trace-event JSON there
    # (Perfetto / chrome://tracing-loadable) at the end of each
    # generate()/fit().
    telemetry: bool = False
    trace_out: Optional[str] = None
    # bounded event ring-buffer size (ONE deque, oldest spans drop
    # first; metrics/drift aggregates are never dropped)
    telemetry_buffer_events: int = 65536
    # drift_report() flags a regime when measured/predicted leaves
    # [1/(1+thr), 1+thr] — 0.5 means "off by more than 1.5x either way"
    telemetry_drift_threshold: float = 0.5
    # live scrape endpoint (utils/telemetry.MetricsServer): serve
    # /metrics (Prometheus text from the engine's lifetime registry)
    # and /healthz from a stdlib http.server thread. None = off;
    # 0 = bind an ephemeral port (the bound port is on
    # engine.metrics_server.port); N = that port. Setting it also
    # enables telemetry (the registry must be live to scrape). The
    # ROADMAP replica-autoscaler polls this. --metrics-port.
    metrics_port: Optional[int] = None
    # bind address for the scrape endpoint: loopback by default (safe
    # on shared hosts); set "0.0.0.0" to expose it to a pod/host
    # network scraper. --metrics-host.
    metrics_host: str = "127.0.0.1"
    # failure flight recorder (docs/observability.md "Failure flight
    # recorder"): when set, ServeEngine (and the router/disagg tiers
    # above it) auto-dump a bounded post-mortem bundle — last-N ring
    # spans, metrics/drift snapshots, memory ledger, scheduler + KV
    # pool state, fault accounting — into this directory on
    # fault-abort, deadline storm, or rung-4 rejection (atomic
    # tmp+rename; rate-limited; loadable by tools/postmortem.py).
    # Setting it implies telemetry (the bundle needs the span ring).
    # postmortem_events bounds the bundle's event payload.
    # --postmortem-dir / --postmortem-events.
    postmortem_dir: Optional[str] = None
    postmortem_events: int = 2048
    # SLO burn-rate monitor (utils/slo.py, rendered by
    # tools/slo_report.py): the tolerated violation fraction of the
    # slo_ttft_ms/slo_tpot_ms targets (0.01 = a 99% SLO). The
    # ReplicaPool auto-arms the monitor whenever SLO targets are set
    # (slo_monitor=False disarms); alerts fire on fast+slow windowed
    # burn rates over exported counters only, deterministic at one
    # seed. --slo-error-budget / --no-slo-monitor.
    slo_error_budget: float = 0.01
    slo_monitor: bool = True

    # ---- async/overlap training runtime (core/overlap.py) ----
    # bucketed, backward-overlapped gradient sync: the walk's weighted
    # ops partition into contiguous buckets of ~this many MiB of master
    # parameters, and each bucket's data-axis gradient all-reduce is
    # anchored (custom_vjp sync point + optimization_barrier) at the
    # point in the backward pass where the bucket's grads complete, so
    # XLA schedules it concurrently with the remaining backward instead
    # of coalescing one monolithic end-of-backward sync. Gradients are
    # BIT-identical either way (same reduction set, donation
    # preserved). 0 = legacy monolithic sync; None (the default) =
    # AUTO-TUNE from the machine model at compile time
    # (core/overlap.resolve_bucket_mb: interconnect bandwidth x the
    # expected backward slice picks the bucket granularity; resolves to
    # 0 when there is no data axis to sync over). Explicit values are
    # authoritative, and the RESOLVED value is what the cost-cache
    # machine fingerprint folds. --grad-bucket-mb.
    grad_bucket_mb: Optional[float] = None
    # pipelined host dispatch (model.fit): keep up to this many train
    # dispatches in flight before retrieving the oldest step's host
    # metrics — depth 2 retrieves step N while step N+1 runs on device.
    # 1 = fully synchronous (block on every step), 0 = unbounded
    # (epoch-bulk retrieval, device metric handles grow with the
    # epoch). --train-dispatch-depth.
    train_dispatch_depth: int = 2

    # auto-parallelization (reference: config.h:116-141)
    search_budget: int = 0
    search_alpha: float = 0.05
    # simulator overlap modeling (reference search_overlap_backward_
    # update, simulator.cc:393-497): when True (default) gradient-sync
    # tasks may overlap the remaining backward pass — bucket-granular
    # when grad_bucket_mb > 0, per-op otherwise; when False every sync
    # serializes after the whole backward. Folded into the cost-cache
    # machine fingerprint, so flipping it can never resurrect stale
    # entries. --no-overlap-sync disables.
    search_overlap_backward_sync: bool = True
    # delta re-simulation (Simulator.simulate_delta): per proposal,
    # re-cost only the moved op(s) and replay the cached scheduled task
    # graph instead of rebuilding + rescheduling everything — the
    # paper's delta simulation algorithm; exact (bit-equal makespans),
    # with periodic full-simulation re-syncs counted in search stats.
    # --no-delta-sim falls back to full simulation per move.
    search_delta_sim: bool = True
    # parallel annealing chains (Python engine): K independent MCMC
    # walks with per-chain seeds derived from `seed`, splitting the
    # TOTAL budget and sharing one read-mostly cost cache; best chain
    # wins. 0 = auto (min(4, cpu_count)).
    search_chains: int = 0
    # persistent per-op cost cache (search/cost_cache.py): serialize
    # simulator costs keyed by (op signature, axis map, machine-model
    # fingerprint) so repeated searches and mesh-shape sweeps skip
    # re-deriving/re-measuring. cost_cache_file=None uses
    # costcache.json under utils/cache_dirs.measurement_cache_dir().
    search_cost_cache: bool = True
    cost_cache_file: Optional[str] = None
    import_strategy_file: Optional[str] = None
    export_strategy_file: Optional[str] = None
    enable_sample_parallel: bool = True
    enable_parameter_parallel: bool = False
    enable_attribute_parallel: bool = False
    # TPU-first additions: new parallel axes (SURVEY.md section 2.4 calls
    # these out as absent from the reference and required here).
    enable_sequence_parallel: bool = False
    # SP attention lowering: "ring" (K/V rotate over ICI, no score
    # materialization — arbitrary lengths), "alltoall" (heads scatter /
    # seq gathers, full-MXU blocks — needs heads % axis == 0), or
    # "auto" (alltoall when heads divide and the per-device score
    # matrix fits; parallel/ulysses.sp_mode_for)
    sp_attention: str = "auto"
    # ZeRO-1: shard dense optimizer slots (momentum/adam moments) over
    # the `data` mesh axis — pure GSPMD annotations (the slot arrays
    # get a data-sharded NamedSharding and the update constrains them
    # to stay there; XLA inserts the reduce-scatter/all-gather), no
    # manual collectives. Cuts optimizer memory by the DP degree.
    zero_optimizer_sharding: bool = False
    enable_expert_parallel: bool = False
    enable_pipeline_parallel: bool = False
    enable_propagation: bool = False
    # search the mesh factorization (parallel DEGREE) too: 8 devices ->
    # dp8 vs dp4xtp2 vs dp2xtp4 ... (the reference samples ND part counts
    # in get_random_parallel_config, model.cc:512; here the degree comes
    # from the mesh, so the search enumerates mesh shapes).
    search_mesh_shapes: bool = False
    # offer device-explicit placement candidates (__devices__ bindings,
    # reference ParallelConfig.device_ids) to the search. OPT-IN: GSPMD
    # executes such strategies as replication (the executable form of
    # per-table placement is DistributedEmbedding's table sharding), so
    # they are for strategy-space exploration/export tooling.
    enable_device_placement: bool = False
    machine_model_file: Optional[str] = None
    # ground the cost model per-op: the top-N ops by analytic time get
    # their fwd/bwd timed as isolated jitted kernels at the strategy's
    # sub-shape (search/op_measure.py — the analog of the reference
    # measuring every op's real kernels at search time, model.cu:20-62).
    # 0 = analytic-only (default: measuring pays a jit compile per
    # distinct op shape on first use; cached per machine thereafter).
    measure_top_ops: int = 0
    # DOT export of the simulated task graph (reference --taskgraph,
    # simulator.cc:508-556); written by the first simulate() of a search.
    taskgraph_file: Optional[str] = None
    # Perfetto export of the WINNING strategy's simulated event-loop
    # schedule (Simulator.export_schedule): per-resource tracks,
    # critical-path flags, exact makespan metadata — the visual twin
    # of a measured --trace-out trace. Written at the end of optimize.
    # --schedule-trace.
    schedule_trace_file: Optional[str] = None
    # per-proposal search tracing (search/trace.SearchTrace): every
    # MCMC proposal (iteration, chain, op moved, delta-cost,
    # accept/reject, delta-vs-full path) lands in a bounded ring with
    # convergence diagnostics (acceptance by phase, best-cost curve)
    # surfaced in search_report / BENCH_search.json. Pure host-side
    # observation: traced and untraced searches are bit-identical at
    # the same seed. The native C++ walk is untraced (its loop lives
    # in csrc/mcmc.cc): use_native=False gets diagnostics there.
    # --no-search-trace disables.
    search_trace: bool = True

    # MoE dispatch path: "auto" uses dense GShard masks (MXU-friendly,
    # clean EP all-to-alls) until the mask would exceed
    # ops/moe.py DENSE_MASK_ELEMENT_LIMIT elements, then switches to
    # sorted-scatter routing (argsort by expert; no (S, E, C) mask —
    # the scalable form for large expert counts). "dense"/"sorted"
    # force a path.
    moe_dispatch: str = "auto"

    # generalized pipeline parallelism (core/staged.py): auto-cut the op
    # graph into this many flops-balanced stages over a matching mesh
    # axis. 0 = off. Strategy device pins trigger staged execution
    # independently of this knob.
    pipeline_stages: int = 0
    pipeline_microbatches: int = 4
    pipeline_schedule: str = "gpipe"
    # interleaved (virtual-stage) 1F1B: each pipe device hosts this
    # many round-robin stage chunks (Megatron interleaving), dividing
    # the warmup/drain bubble by up to v. Requires
    # pipeline_schedule="1f1b" with auto-cut stages; 1 = off.
    pipeline_virtual_stages: int = 1

    # fusion (reference: --fusion flag, model.cc:1472)
    perform_fusion: bool = False

    # sibling-conv batching: convs that read the SAME tensor with the
    # SAME geometry (the 1x1 branch heads of an Inception module)
    # execute as ONE conv with their kernels concatenated along
    # channel-out, outputs sliced back per branch. Exact numerics (each
    # output channel's contraction is unchanged); the win is MXU lane
    # occupancy — three couts of 192/160/160 pad to 256 lanes each
    # (25-37% waste) where the merged 512 tiles perfectly. No reference
    # analog (cuDNN picks per-conv algorithms instead,
    # conv_2d.cu:173-260); this is the TPU-shaped counterpart.
    sibling_conv_fusion: bool = True

    # remat: trade FLOPs for HBM (no reference analog; TPU-first)
    remat: bool = False

    # compute layout for Conv2D/Pool2D/BatchNorm: "NCHW" (logical, the
    # reference's layout) or "NHWC" (channels on the TPU lane dim; ops
    # transpose at their boundaries and XLA cancels the interior pairs).
    conv_layout: str = "NCHW"

    # multi-step dispatch body: "auto" unrolls the K steps (instead of
    # lax.scan) only when donated params are a large fraction of device
    # memory — a TPU scan carry is double-buffered, so at DLRM scale
    # (26x1M-row tables) the scanned program needs 2x-table scratch and
    # OOMs a chip the unrolled/single-step program fits. True/False
    # force either body.
    multi_step_unroll: object = "auto"

    # sparse embedding updates: when the optimizer's exact rule can be
    # applied row-wise (SGD, no momentum/decay), embedding tables whose
    # index tensors are graph inputs skip the dense-gradient sweep and
    # get a scatter update over the touched rows only (reference analog:
    # scatter-add embedding backward, src/ops/embedding.cu; essential
    # for DLRM-scale vocabularies where a dense step writes GBs).
    sparse_embedding_updates: bool = True

    # opt-in: also use the sparse path when the optimizer only has a
    # LAZY sparse form (SGD+momentum, Adam): touched rows get the exact
    # rule on coalesced gradients, untouched rows keep stale state
    # (momentum does not decay, Adam m/v do not advance) — the
    # torch.optim.SparseAdam trade. Off by default because it changes
    # optimizer semantics, not just cost.
    sparse_embedding_lazy: bool = False

    # ---- serving (flexflow_tpu.serve) ----
    # block-paged KV-cache geometry: the pool holds kv_num_pages pages
    # of kv_page_size tokens each, per layer; page 0 is reserved as the
    # write sink for padding lanes (serve/kv_cache.py). Sized so
    # (kv_num_pages - 1) * kv_page_size bounds the total resident
    # tokens across all concurrent sequences.
    kv_page_size: int = 16
    kv_num_pages: int = 257
    # KV-page storage format (serve/kv_cache.py): "float32" (exact),
    # "bfloat16" (rounds on write; exact for bf16-activation engines),
    # or "int8" (per-page scale arrays, quantize-on-write /
    # dequantize-at-read in the ragged kernel). Quantized pages cost
    # ~1/4 the bytes, so an equal byte budget holds ~2-4x the pages —
    # the concurrent-sequences-per-chip lever. The serving exactness
    # gate relaxes for lossy formats to bounded attention-output error
    # + greedy token parity (tests/test_kv_quant.py). --kv-dtype.
    kv_dtype: str = "float32"
    # size the page pool by BYTE budget instead of page count: when
    # > 0, kv_num_pages derives as 1 + budget // page_bytes(kv_dtype) —
    # computed from the configured dtype's itemsize (+ scale rows), so
    # flipping kv_dtype at a fixed budget changes the PAGE COUNT, and
    # every page-fraction knob (admission watermark, degradation-ladder
    # rungs) automatically sees the larger effective pool. 0 = use
    # kv_num_pages directly. --kv-pool-mb.
    kv_pool_mb: float = 0.0
    # hierarchical prefix-cache tier (serve/host_tier.py): byte budget
    # of the host-RAM page store below the HBM pool. When > 0 (and
    # serve_host_tier is on), LRU pages evicted under pressure spill
    # their bytes to host memory instead of being discarded, and a
    # later prefix match re-imports them when the priced DMA time
    # (TPUMachineModel.host_transfer) beats recompute. A ReplicaPool
    # shares ONE store across replicas. 0 = tier unarmed.
    # --host-tier-mb / --no-host-tier.
    host_tier_mb: float = 0.0
    serve_host_tier: bool = True
    # ragged-attention kv-block shape (kernels/paged_ragged_v2.py): KV
    # tokens each (run of lanes, kv-block) work item covers (rounded
    # to whole pages). 0 = the autotune-by-shape table
    # (choose_block_kv). --serve-attn-block-kv.
    serve_attn_block_kv: int = 0
    # AOT program cache directory (core/programs.py): serving engines
    # snapshot their compiled executables here keyed by a program
    # fingerprint (arch + lane widths + kv geometry + adapter + tp +
    # jax/backend version), and a cold engine — an autoscaler scale-up
    # with no parked replica, a fresh process — deserializes them
    # before the first request instead of paying the compile storm.
    # None = compile per process. --program-cache-dir.
    program_cache_dir: Optional[str] = None
    # continuous-batching scheduler caps (serve/scheduler.py): at most
    # serve_max_seqs sequences hold decode slots at once (this is also
    # the decode-lane reserve of the engine's single mixed step), and
    # one scheduler step computes at most serve_prefill_budget prompt
    # tokens of prefill work (FCFS; long prompts chunk across steps).
    serve_max_seqs: int = 8
    serve_prefill_budget: int = 512
    # The engine's step is chunked prefill (serve/engine.py): prompt
    # chunks from any number of requests pack together with every
    # running decode token into ONE fixed-shape program of
    # serve_prefill_budget + serve_max_seqs lanes — zero per-bucket
    # recompiles, decode never stalls behind a long prompt.
    # prefix caching (serve/kv_cache.py): completed KV pages are
    # content-hashed and shared copy-free across sequences via per-page
    # refcounts, so a prompt whose prefix is already resident skips
    # those tokens at prefill. --no-prefix-cache.
    serve_prefix_cache: bool = True
    # admission watermark (fraction of the page pool that must stay
    # reclaimable after admitting a request's first chunk): with
    # on-demand page allocation the scheduler admits against ACTUAL
    # residency, and this headroom keeps admissions from thrashing the
    # preemption path the moment running sequences grow.
    serve_admit_watermark: float = 0.02
    # speculative decoding (serve/speculative.py): a host-side drafter
    # (prompt-lookup n-gram by default) proposes up to serve_spec_tokens
    # continuation tokens per decoding sequence per step; the mixed
    # program verifies them in spare lanes and the host keeps the
    # longest matching prefix — greedy outputs stay token-identical to
    # sequential decode. Draft length adapts per request from a
    # windowed acceptance rate (0 = auto-disabled on adversarial
    # text). Draft lanes compete with prefill chunks for
    # serve_prefill_budget; decode lanes never starve.
    # --spec-tokens N / --no-spec-decode.
    serve_spec_decode: bool = True
    serve_spec_tokens: int = 4
    # ---- robustness (utils/faults.py, docs/robustness.md) ----
    # deterministic fault injection: a spec string like
    # "serve.mixed:transient@2,5;serve.page_pressure:exhaust:0.5@3-9"
    # arms seeded failures at marked sites (engine dispatch, scheduler
    # page pressure, checkpoint commit) so chaos tests replay exactly.
    # None = no injection (also settable via FLEXFLOW_TPU_FAULTS).
    fault_spec: Optional[str] = None
    # default per-request wall-clock deadline in seconds for
    # ServeEngine.generate (0 = none): a request that has not finished
    # when its deadline passes is aborted at the next chunk boundary
    # with outcome "deadline_expired", its pages reclaimed.
    serve_request_deadline: float = 0.0
    # bounded retry-with-backoff around the engine's jitted dispatch
    # for TransientError (injected or a flaky link): up to
    # serve_max_retries re-dispatches, sleeping
    # serve_retry_backoff_s * 2^attempt between them.
    serve_max_retries: int = 3
    serve_retry_backoff_s: float = 0.02
    # graceful-degradation ladder under page pressure
    # (serve/scheduler.py): rung 1 sheds speculation, rung 2 stops
    # prefix-matching + shrinks the parked LRU, rung 3 tightens the
    # admission watermark (floored at 8% of the pool), rung 4 rejects
    # (structured RejectedRequest)
    # what can never fit. --no-degrade-ladder freezes rung 0 behavior.
    serve_degrade_ladder: bool = True
    # opt-in online-serving rung-4 policy: reject the waiting head
    # after this many consecutive stalled admission attempts at rung
    # >= 3 (0 = never reject for stalling; offline batches wait).
    serve_reject_stalls: int = 0
    # tensor-parallel sharded serving (docs/serving.md "Sharded
    # serving"): shard the ONE mixed program over a 1-D "tensor" mesh —
    # head-parallel attention over a head-sharded KV page pool,
    # column/row-parallel projections with one all-reduce after the
    # attention output and FFN, vocab-sharded embedding/head with ONE
    # logits all-gather. "" (default) = single device; an integer
    # string = that tensor-parallel degree; "auto" = resolve the degree
    # through the placement search (search/serve_place.optimize_serve —
    # the SOAP-style simulator pricing applied to the serve program).
    # --serve-mesh.
    serve_mesh: str = ""
    # disaggregated prefill/decode serving (serve/disagg.py,
    # docs/serving.md "Disaggregated serving"): dedicated prefill
    # engines stream finished KV pages to dedicated decode engines
    # over a host-side page handoff, so decode steps stop paying for
    # the prefill budget's lanes (the TPOT tax of the ONE mixed
    # program). --serve-disagg enables it; serve_disagg_ratio is
    # "P:D" engine counts ("" = 1:1, "auto" = the placement search's
    # ratio table via optimize_serve(..., disaggregated=True) — the
    # SOAP don't-hand-tune-it discipline on a new axis);
    # serve_disagg_decode_budget is the decode role's prefill-lane
    # stub (tokens; 0 = 2 pages' worth — just enough to recompute a
    # handoff's partial tail page). --serve-disagg-ratio /
    # --serve-disagg-decode-budget.
    serve_disagg: bool = False
    serve_disagg_ratio: str = ""
    serve_disagg_decode_budget: int = 0
    # multi-replica serving tier (serve/router.py, docs/serving.md
    # "Multi-replica routing"): N engine replicas behind a request
    # router. serve_replicas sizes the starting pool
    # (--serve-replicas): an integer, or "auto" to resolve the
    # (tensor degree, replica count) shape through the 2-D serve-mesh
    # search (search/serve_place.optimize_serve_mesh, docs/search.md
    # "2-D serve mesh") — with --serve-mesh N the degree is pinned and
    # only the replica count is searched; with --serve-mesh auto the
    # ONE walk prices both. router_policy picks how requests land —
    # "affinity" routes to the replica whose chain-hash prefix
    # registry holds the LONGEST matching prefix of the prompt (a
    # host-side dict probe per page-aligned block; tenant-sticky
    # fallback hash when nothing matches, load-aware spill off
    # rung-3/occupancy pressure), "round_robin" is the A/B baseline
    # (--router-policy). slo_ttft_ms / slo_tpot_ms define
    # goodput-under-SLO — a request counts only when its TTFT and
    # per-token decode latency both meet target (0 = that bound is
    # waived) (--slo-ttft-ms / --slo-tpot-ms). serve_autoscale arms
    # the telemetry-driven replica autoscaler (TTFT/TPOT p99 +
    # pool-occupancy gauges vs the SLOs, priced against the placement
    # search's per-degree decode table; --autoscale), scaling between
    # 1 and serve_autoscale_max replicas (0 = 2x serve_replicas).
    serve_replicas: Union[int, str] = 1
    router_policy: str = "affinity"
    slo_ttft_ms: float = 0.0
    slo_tpot_ms: float = 0.0
    serve_autoscale: bool = False
    serve_autoscale_max: int = 0
    # wall-clock serving fabric (docs/serving.md "Wall-clock mode"):
    # serve_wall_clock switches ReplicaPool.run to real time — each
    # replica steps on its own worker thread, arrivals pace on the
    # wall clock, and goodput-under-SLO is a measured wall number
    # (tokens stay identical to the virtual-clock run at one seed;
    # the autoscaler stays virtual-only). --wall-clock.
    # serve_transport moves disagg PageShipments across a
    # length-prefixed socket ("tcp"; "" = in-process handoff) with
    # the receiver enforcing the SAME serve_admit_watermark
    # backpressure; host/port pick the loopback receiver's bind
    # (port 0 = ephemeral). --transport / --transport-port.
    serve_wall_clock: bool = False
    serve_transport: str = ""
    serve_transport_host: str = "127.0.0.1"
    serve_transport_port: int = 0
    # multi-tenant LoRA adapter serving (serve/adapters.py,
    # docs/serving.md "Multi-tenant adapters"): adapter_rank > 0 arms
    # the HBM-resident adapter pool — fixed rank-padded (A, B) slab
    # pairs, one slot per resident tenant, gathered per lane inside
    # the ONE mixed program so tenant-heterogeneous batches decode in
    # one fixed-shape step (zero recompiles).
    # adapter_pool_mb sizes the slot count by per-device byte budget
    # (the kv_pool_mb idiom; 0 = 1 + serve_max_seqs slots).
    # tenant_adapters is the synthetic tenant count traffic mixes and
    # the lora bench register (tenants 1..N, serve/traffic.py).
    # --adapter-rank / --adapter-pool-mb / --tenant-adapters.
    adapter_rank: int = 0
    adapter_pool_mb: float = 0.0
    tenant_adapters: int = 4

    # synthetic input when no dataset is provided (reference: config.h:131)
    synthetic_input: bool = False

    # mesh description: axis names/sizes. None = single device.
    mesh_shape: Optional[Sequence[int]] = None
    mesh_axes: Optional[Sequence[str]] = None

    iter_config: FFIterationConfig = dataclasses.field(
        default_factory=FFIterationConfig
    )

    # argv to parse at construction; None = don't touch the process argv
    # (a library must not hijack the host application's flags). Use
    # FFConfig.from_args() in driver scripts for reference CLI parity.
    argv: Optional[Sequence[str]] = None

    def __post_init__(self):
        if self.argv is not None:
            self.parse_args(self.argv)
        self.validate()

    def validate(self) -> None:
        """Reject silently-ignorable values (conv_layout falls back to
        NCHW on any non-"NHWC" string, which would be an undetectable
        perf misconfiguration). Called from __post_init__ and compile."""
        # normalize the precision policy to jnp dtypes (CLI hands us
        # strings like "bfloat16"); reject non-float dtypes loudly — an
        # int compute_dtype would silently break every cast site
        from .core.precision import resolve_dtype
        self.compute_dtype = resolve_dtype(self.compute_dtype,
                                           "compute_dtype")
        self.param_dtype = resolve_dtype(self.param_dtype, "param_dtype")
        if self.conv_layout not in ("NCHW", "NHWC"):
            raise ValueError(
                f"conv_layout must be 'NCHW' or 'NHWC', got "
                f"{self.conv_layout!r}")
        if self.pipeline_schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"pipeline_schedule must be 'gpipe' or '1f1b', got "
                f"{self.pipeline_schedule!r}")
        if self.moe_dispatch not in ("auto", "dense", "sorted"):
            raise ValueError(
                f"moe_dispatch must be 'auto', 'dense' or 'sorted', "
                f"got {self.moe_dispatch!r}")
        if self.sp_attention not in ("auto", "ring", "alltoall"):
            raise ValueError(
                f"sp_attention must be 'auto', 'ring' or 'alltoall', "
                f"got {self.sp_attention!r}")
        if self.pipeline_virtual_stages < 1:
            raise ValueError(
                f"pipeline_virtual_stages must be >= 1, got "
                f"{self.pipeline_virtual_stages}")
        if self.grad_bucket_mb is not None and self.grad_bucket_mb < 0:
            raise ValueError(
                f"grad_bucket_mb must be >= 0 (0 = monolithic sync, "
                f"unset = auto-tune), got {self.grad_bucket_mb}")
        if self.train_dispatch_depth < 0:
            raise ValueError(
                f"train_dispatch_depth must be >= 0 (0 = unbounded, "
                f"1 = synchronous), got {self.train_dispatch_depth}")
        if self.search_chains < 0:
            raise ValueError(
                f"search_chains must be >= 0 (0 = auto), got "
                f"{self.search_chains}")
        if self.kv_page_size < 1:
            raise ValueError(
                f"kv_page_size must be >= 1, got {self.kv_page_size}")
        if self.kv_num_pages < 2:
            raise ValueError(
                f"kv_num_pages must be >= 2 (page 0 is the serving "
                f"sink page), got {self.kv_num_pages}")
        if self.kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {KV_DTYPES}, "
                f"got {self.kv_dtype!r}")
        if self.kv_pool_mb < 0:
            raise ValueError(
                f"kv_pool_mb must be >= 0 (0 = size by kv_num_pages), "
                f"got {self.kv_pool_mb}")
        if self.host_tier_mb < 0:
            raise ValueError(
                f"host_tier_mb must be >= 0 (0 = host tier unarmed), "
                f"got {self.host_tier_mb}")
        if self.serve_attn_block_kv < 0:
            raise ValueError(
                f"serve_attn_block_kv must be >= 0 (0 = autotune), "
                f"got {self.serve_attn_block_kv}")
        if self.serve_max_seqs < 1:
            raise ValueError(
                f"serve_max_seqs must be >= 1, got {self.serve_max_seqs}")
        if self.serve_prefill_budget < 1:
            raise ValueError(
                f"serve_prefill_budget must be >= 1, got "
                f"{self.serve_prefill_budget}")
        if self.adapter_rank < 0:
            raise ValueError(
                f"adapter_rank must be >= 0 (0 = adapters unarmed), "
                f"got {self.adapter_rank}")
        if self.adapter_pool_mb < 0:
            raise ValueError(
                f"adapter_pool_mb must be >= 0 (0 = size by "
                f"serve_max_seqs), got {self.adapter_pool_mb}")
        if self.tenant_adapters < 0:
            raise ValueError(
                f"tenant_adapters must be >= 0, got "
                f"{self.tenant_adapters}")
        if not 0.0 <= self.serve_admit_watermark < 1.0:
            raise ValueError(
                f"serve_admit_watermark must be in [0, 1), got "
                f"{self.serve_admit_watermark}")
        if self.serve_spec_tokens < 0:
            raise ValueError(
                f"serve_spec_tokens must be >= 0 (0 disables "
                f"speculative decoding), got {self.serve_spec_tokens}")
        if self.serve_request_deadline < 0:
            raise ValueError(
                f"serve_request_deadline must be >= 0 (0 = none), got "
                f"{self.serve_request_deadline}")
        if self.serve_max_retries < 0:
            raise ValueError(
                f"serve_max_retries must be >= 0, got "
                f"{self.serve_max_retries}")
        if self.serve_retry_backoff_s < 0:
            raise ValueError(
                f"serve_retry_backoff_s must be >= 0, got "
                f"{self.serve_retry_backoff_s}")
        if self.serve_reject_stalls < 0:
            raise ValueError(
                f"serve_reject_stalls must be >= 0 (0 = never), got "
                f"{self.serve_reject_stalls}")
        sr = str(self.serve_disagg_ratio or "").strip()
        if sr and sr != "auto":
            parts = sr.split(":")
            ok = len(parts) == 2
            if ok:
                try:
                    ok = int(parts[0]) >= 1 and int(parts[1]) >= 1
                except ValueError:
                    ok = False
            if not ok:
                raise ValueError(
                    f"serve_disagg_ratio must be '', 'auto', or "
                    f"'P:D' with positive engine counts, got "
                    f"{self.serve_disagg_ratio!r}")
        if self.serve_disagg_decode_budget < 0:
            raise ValueError(
                f"serve_disagg_decode_budget must be >= 0 (0 = two "
                f"pages' worth), got {self.serve_disagg_decode_budget}")
        if isinstance(self.serve_replicas, str):
            if self.serve_replicas.strip() != "auto":
                raise ValueError(
                    f"serve_replicas must be an integer >= 1 or "
                    f"'auto', got {self.serve_replicas!r}")
        elif self.serve_replicas < 1:
            raise ValueError(
                f"serve_replicas must be >= 1, got "
                f"{self.serve_replicas}")
        if self.router_policy not in ("affinity", "round_robin"):
            raise ValueError(
                f"router_policy must be 'affinity' or 'round_robin', "
                f"got {self.router_policy!r}")
        if self.slo_ttft_ms < 0 or self.slo_tpot_ms < 0:
            raise ValueError(
                f"slo_ttft_ms/slo_tpot_ms must be >= 0 (0 = no "
                f"bound), got {self.slo_ttft_ms}/{self.slo_tpot_ms}")
        if self.serve_autoscale_max < 0:
            raise ValueError(
                f"serve_autoscale_max must be >= 0 (0 = 2x "
                f"serve_replicas), got {self.serve_autoscale_max}")
        if str(self.serve_transport or "").strip() not in ("", "tcp"):
            raise ValueError(
                f"serve_transport must be '' (in-process) or 'tcp', "
                f"got {self.serve_transport!r}")
        if not 0 <= int(self.serve_transport_port) <= 65535:
            raise ValueError(
                f"serve_transport_port must be 0..65535 (0 = "
                f"ephemeral), got {self.serve_transport_port}")
        if self.serve_wall_clock and self.serve_autoscale:
            raise ValueError(
                "--wall-clock and --autoscale are mutually exclusive: "
                "the autoscaler replays on the virtual clock only")
        sm = str(self.serve_mesh or "").strip()
        if sm and sm != "auto":
            try:
                ok = int(sm) >= 1
            except ValueError:
                ok = False
            if not ok:
                raise ValueError(
                    f"serve_mesh must be '', 'auto', or a positive "
                    f"tensor-parallel degree, got {self.serve_mesh!r}")
        if self.telemetry_buffer_events < 1:
            raise ValueError(
                f"telemetry_buffer_events must be >= 1, got "
                f"{self.telemetry_buffer_events}")
        if self.telemetry_drift_threshold < 0:
            raise ValueError(
                f"telemetry_drift_threshold must be >= 0, got "
                f"{self.telemetry_drift_threshold}")
        if self.metrics_port is not None and not (
                0 <= int(self.metrics_port) <= 65535):
            raise ValueError(
                f"metrics_port must be None (off) or 0..65535 "
                f"(0 = ephemeral), got {self.metrics_port}")
        if self.postmortem_events < 1:
            raise ValueError(
                f"postmortem_events must be >= 1, got "
                f"{self.postmortem_events}")
        if not (0.0 < self.slo_error_budget <= 1.0):
            raise ValueError(
                f"slo_error_budget must be in (0, 1] (the tolerated "
                f"violation fraction), got {self.slo_error_budget}")
        if self.fault_spec:
            # parse eagerly so a typo'd spec fails at config time, not
            # silently mid-chaos-run
            from .utils.faults import FaultSpec
            FaultSpec(self.fault_spec)
        if self.pipeline_virtual_stages > 1 \
                and self.pipeline_schedule != "1f1b":
            raise ValueError(
                "pipeline_virtual_stages > 1 requires "
                "pipeline_schedule='1f1b' (interleaving lives in the "
                "explicit-gradient schedule)")

    @classmethod
    def from_args(cls, argv: Optional[Sequence[str]] = None) -> "FFConfig":
        """Reference-style construction: parse CLI flags
        (FFConfig::parse_args, model.cc:2258-2379)."""
        return cls(argv=list(sys.argv[1:]) if argv is None else list(argv))

    # -- CLI parity (reference: FFConfig::parse_args model.cc:2258-2379) --
    _FLAG_MAP = {
        "-b": ("batch_size", int),
        "--batch-size": ("batch_size", int),
        "-e": ("epochs", int),
        "--epochs": ("epochs", int),
        "--iterations": ("iterations", int),
        "-lr": ("learning_rate", float),
        "--learning-rate": ("learning_rate", float),
        "-wd": ("weight_decay", float),
        "--weight-decay": ("weight_decay", float),
        "--search-budget": ("search_budget", int),
        "--budget": ("search_budget", int),
        "--search-alpha": ("search_alpha", float),
        "--alpha": ("search_alpha", float),
        "--search-chains": ("search_chains", int),
        "--cost-cache": ("cost_cache_file", str),
        "--import": ("import_strategy_file", str),
        "--import-strategy": ("import_strategy_file", str),
        "--export": ("export_strategy_file", str),
        "--export-strategy": ("export_strategy_file", str),
        "--machine-model-file": ("machine_model_file", str),
        "--taskgraph": ("taskgraph_file", str),
        "--seed": ("seed", int),
        "--grad-bucket-mb": ("grad_bucket_mb", float),
        "--train-dispatch-depth": ("train_dispatch_depth", int),
        "--compute-dtype": ("compute_dtype", str),
        "--param-dtype": ("param_dtype", str),
        "--conv-layout": ("conv_layout", str),
        "--measure-ops": ("measure_top_ops", int),
        "--moe-dispatch": ("moe_dispatch", str),
        "--sp-attention": ("sp_attention", str),
        "--pipeline-stages": ("pipeline_stages", int),
        "--pipeline-microbatches": ("pipeline_microbatches", int),
        "--pipeline-schedule": ("pipeline_schedule", str),
        "--pipeline-virtual-stages": ("pipeline_virtual_stages", int),
        "--kv-page-size": ("kv_page_size", int),
        "--kv-num-pages": ("kv_num_pages", int),
        "--kv-dtype": ("kv_dtype", str),
        "--kv-pool-mb": ("kv_pool_mb", float),
        "--host-tier-mb": ("host_tier_mb", float),
        "--program-cache-dir": ("program_cache_dir", str),
        "--serve-attn-block-kv": ("serve_attn_block_kv", int),
        "--serve-max-seqs": ("serve_max_seqs", int),
        "--serve-prefill-budget": ("serve_prefill_budget", int),
        "--adapter-rank": ("adapter_rank", int),
        "--adapter-pool-mb": ("adapter_pool_mb", float),
        "--tenant-adapters": ("tenant_adapters", int),
        "--serve-admit-watermark": ("serve_admit_watermark", float),
        "--spec-tokens": ("serve_spec_tokens", int),
        "--fault-spec": ("fault_spec", str),
        "--request-deadline": ("serve_request_deadline", float),
        "--serve-max-retries": ("serve_max_retries", int),
        "--serve-retry-backoff": ("serve_retry_backoff_s", float),
        "--serve-reject-stalls": ("serve_reject_stalls", int),
        "--serve-mesh": ("serve_mesh", str),
        "--serve-disagg-ratio": ("serve_disagg_ratio", str),
        "--serve-disagg-decode-budget": ("serve_disagg_decode_budget",
                                         int),
        "--serve-replicas": ("serve_replicas", _int_or_auto),
        "--router-policy": ("router_policy", str),
        "--slo-ttft-ms": ("slo_ttft_ms", float),
        "--slo-tpot-ms": ("slo_tpot_ms", float),
        "--autoscale-max": ("serve_autoscale_max", int),
        "--transport": ("serve_transport", str),
        "--transport-host": ("serve_transport_host", str),
        "--transport-port": ("serve_transport_port", int),
        "--trace-out": ("trace_out", str),
        "--trace-dir": ("trace_dir", str),
        "--telemetry-buffer": ("telemetry_buffer_events", int),
        "--drift-threshold": ("telemetry_drift_threshold", float),
        "--metrics-port": ("metrics_port", int),
        "--metrics-host": ("metrics_host", str),
        "--schedule-trace": ("schedule_trace_file", str),
        "--postmortem-dir": ("postmortem_dir", str),
        "--postmortem-events": ("postmortem_events", int),
        "--slo-error-budget": ("slo_error_budget", float),
    }
    _BOOL_FLAGS = {
        "--profiling": "profiling",
        "--fusion": "perform_fusion",
        "--remat": "remat",
        "--overlap": "search_overlap_backward_sync",
        "--enable-parameter-parallel": "enable_parameter_parallel",
        "--enable-attribute-parallel": "enable_attribute_parallel",
        "--enable-sample-parallel": "enable_sample_parallel",
        "--enable-sequence-parallel": "enable_sequence_parallel",
        "--enable-expert-parallel": "enable_expert_parallel",
        "--enable-pipeline-parallel": "enable_pipeline_parallel",
        "--enable-propagation": "enable_propagation",
        "--search-mesh-shapes": "search_mesh_shapes",
        "--enable-device-placement": "enable_device_placement",
        "--zero": "zero_optimizer_sharding",
        "--synthetic-input": "synthetic_input",
        "--sparse-embedding-lazy": "sparse_embedding_lazy",
        "--telemetry": "telemetry",
        "--serve-disagg": "serve_disagg",
        "--autoscale": "serve_autoscale",
        "--wall-clock": "serve_wall_clock",
    }
    _NEG_BOOL_FLAGS = {
        "--no-overlap-sync": "search_overlap_backward_sync",
        "--no-sparse-embedding": "sparse_embedding_updates",
        "--no-sibling-conv-fusion": "sibling_conv_fusion",
        "--no-delta-sim": "search_delta_sim",
        "--no-cost-cache": "search_cost_cache",
        "--no-prefix-cache": "serve_prefix_cache",
        "--no-host-tier": "serve_host_tier",
        "--no-spec-decode": "serve_spec_decode",
        "--no-degrade-ladder": "serve_degrade_ladder",
        "--no-search-trace": "search_trace",
        "--no-slo-monitor": "slo_monitor",
    }

    def parse_args(self, argv: Sequence[str]) -> None:
        i = 0
        argv = list(argv)
        while i < len(argv):
            a = argv[i]
            if a in self._FLAG_MAP and i + 1 < len(argv):
                field, typ = self._FLAG_MAP[a]
                setattr(self, field, typ(argv[i + 1]))
                i += 2
                continue
            if a in self._BOOL_FLAGS:
                setattr(self, self._BOOL_FLAGS[a], True)
                i += 1
                continue
            if a in self._NEG_BOOL_FLAGS:
                setattr(self, self._NEG_BOOL_FLAGS[a], False)
                i += 1
                continue
            if a == "--seq-length" and i + 1 < len(argv):
                self.iter_config.seq_length = int(argv[i + 1])
                i += 2
                continue
            i += 1

    # -- device/mesh introspection --
    @property
    def workers_per_node(self) -> int:
        return jax.local_device_count()

    @property
    def num_nodes(self) -> int:
        return jax.process_count()

    @property
    def num_devices(self) -> int:
        return jax.device_count()
