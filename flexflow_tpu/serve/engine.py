"""ServeEngine: one jitted MIXED step over a paged KV-cache.

Wraps an LM built by models/transformer.build_transformer_lm into the
serving hot path. The engine runs ONE program:

  mixed — a fixed-width batch of `serve_prefill_budget + serve_max_seqs`
    LANES, each lane one (sequence, position) query token. Prompt
    chunks from any number of requests and the single decode token of
    every running sequence pack into the same step: K/V for all lanes
    scatters into each sequence's pages, then every lane attends
    through its page-table row masked at its own position + 1
    (kernels/paged_ragged_v2.paged_attention_ragged_v2), so causality is
    exact and decode lanes never stall behind a long prompt. Logits
    reduce to a greedy argmax plus a static top-k head (for seeded
    temperature / top-k sampling) before leaving the device.

Static shapes are the whole game on TPU: the mixed step has ONE
geometry, so XLA compiles ONE serving program — ever. After `warmup()` a
serving process never recompiles (generate() can assert this via
`compile_counts()`), which is what keeps p99 latency flat.

Speculative decoding (serve/speculative.py, docs/serving.md) spends
spare prefill-budget lanes of the SAME program: a host-side drafter
appends up to `serve_spec_tokens` proposed tokens after a sequence's
decode lane, verification keeps the longest prefix matching what the
model would have emitted anyway (plus the correction/bonus token that
told us so), and rejected tokens' pages roll back — several tokens per
dispatch on repetitive text, token-identical output always, zero new
program shapes.

The engine owns a PERSISTENT PagedKVCache and its device pool
(`self.pool`, a serve/kv_cache.KVPool — the one type that knows the
pool's layout): prefix pages committed by one generate() call are
matchable by the next, so a shared system preamble is computed once per
process, not once per batch. The pool flows functionally: the jitted
programs take it donated and return the updated one, so the update is
in-place on device and the host never holds two copies.

The engine reads weights straight out of the compiled FFModel's
TrainState and re-implements the block math as pure functions — the
graph executor has no notion of carried state, and threading a cache
through it would force every op to learn about sequence position. The
ops' numerics are mirrored exactly (LayerNorm f32 statistics, f32
matmul accumulation), so `generate_reference` (naive no-cache
re-forward each step) produces identical greedy tokens — the parity
test, which holds through prefix-cache hits, chunked prefill, and
preemption/resume.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import CompMode
from ..core.programs import ProgramRegistry, boot_phases
from ..kernels.paged_ragged_v2 import (Q_ROWS, choose_block_kv,
                                       ragged_dispatch_passes,
                                       resolve_paged_impl)
from ..parallel.mesh import TENSOR, replica_devices, serve_tensor_mesh
from ..utils.faults import FaultInjector, TransientError, injector_for
from ..utils.telemetry import (SETUP_THREAD, Telemetry, pow2_bucket,
                               roots_s, serve_metrics, telemetry_for)
from . import mixers
from .arch import _dense, describe
from .kv_cache import (HybridPool, KVCacheConfig, KVPool, PagedKVCache,
                       kv_storage_dtype)
from .mixers import LIVE_COUNTS, SELECT_COUNTS  # noqa: F401 (importable here)
from .scheduler import (ChunkPlan, ContinuousBatchingScheduler, Request,
                        RequestOutcome, RequestState, SampleParams)

# pad bias for vocab columns the head padding invents (vocab % t != 0):
# a padded logit must never win argmax or enter the top-k window
_PAD_LOGIT_BIAS = -1e30


def _tree_bytes(tree) -> int:
    """Bytes of a pytree's arrays (shapes alone: nothing is fetched)."""
    return int(sum(x.nbytes for x in jax.tree.leaves(tree)
                   if hasattr(x, "nbytes")))


def probe_serve_arch(model, config=None, context=None):
    """The ServeArch a ServeEngine over ``model`` + ``config`` would
    price, WITHOUT building the engine — what ReplicaPool's 2-D mesh
    resolution (``--serve-replicas auto``) feeds
    search/serve_place.optimize_serve_mesh before any replica exists
    (the searched degree decides how the first engine is built, so
    the arch must be priceable engine-free). Same model introspection
    as ServeEngine._read_arch / serve_arch: decode lanes = the slot
    reserve, prefill lanes = the budget, steady-state context = 3/4
    of the learned positions, adapter-pool geometry from the
    --adapter-* knobs via AdapterConfig.from_ff."""
    from ..search.cost_model import ServeArch
    from .kv_cache import QUANTIZED_KV_DTYPES
    cfg = config if config is not None else model.config
    if model.state is None:
        from ..config import CompMode
        model.compile(comp_mode=CompMode.INFERENCE)
    ops = {op.name: op for op in model.ops}
    for required in ("tok_embed", "pos_embed", "lm_head"):
        if required not in ops:
            raise ValueError(
                f"serve placement needs a build_transformer_lm-shaped "
                f"model (missing op {required!r})")
    num_layers = 0
    while f"layer{num_layers}_attn" in ops:
        num_layers += 1
    if num_layers == 0:
        raise ValueError("model has no layer{i}_attn blocks")
    attn0 = ops["layer0_attn"]
    act_dtype = jnp.dtype(ops["tok_embed"].out_dtype)
    ff_dim = int(model.state.params["layer0_ff1"]["kernel"].shape[1])
    max_seq = int(ops["pos_embed"].num_entries)
    kv_name = str(getattr(cfg, "kv_dtype", "float32"))
    acfg = None
    if int(getattr(cfg, "adapter_rank", 0) or 0) > 0:
        from .adapters import AdapterConfig
        acfg = AdapterConfig.from_ff(
            cfg, num_layers=num_layers, hidden=attn0.embed_dim,
            num_heads=attn0.num_heads, head_dim=attn0.head_dim,
            ff_dim=ff_dim, act_itemsize=int(act_dtype.itemsize))
    return ServeArch(
        num_layers=num_layers, hidden=attn0.embed_dim,
        num_heads=attn0.num_heads, head_dim=attn0.head_dim,
        ff_dim=ff_dim, vocab=int(ops["tok_embed"].num_entries),
        decode_lanes=int(getattr(cfg, "serve_max_seqs", 8)),
        prefill_lanes=int(getattr(cfg, "serve_prefill_budget", 512)),
        context=int(context if context is not None
                    else max(1, max_seq * 3 // 4)),
        kv_dtype=kv_name,
        kv_itemsize=float(kv_storage_dtype(kv_name).itemsize),
        kv_scales=kv_name in QUANTIZED_KV_DTYPES,
        act_itemsize=float(act_dtype.itemsize),
        act_dtype=str(act_dtype.name),
        adapter_rank=acfg.rank if acfg is not None else 0,
        adapter_slots=acfg.num_slots if acfg is not None else 0)


class ServeEngine:
    """Continuous-batching generation over a build_transformer_lm model.

    model must be compiled (any comp_mode); if not, it is compiled here
    in INFERENCE mode (no optimizer slots). All serving knobs come from
    the model's FFConfig (kv_page_size / kv_num_pages / serve_max_seqs /
    serve_prefill_budget / serve_prefix_cache / serve_admit_watermark);
    `prefix_cache` overrides the config (tools that A/B the
    optimisation build two engines over one model).
    """

    # static top-k head width: sampling draws from the top
    # min(TOPK_CAP, vocab) logits of a lane, so the sampled stream
    # leaves the device at fixed shape and the zero-recompile contract
    # survives sampling. top_k > this cap is rejected at generate().
    TOPK_CAP = 64

    # failure flight recorder thresholds: deadline expirations at ONE
    # chunk-boundary sweep that count as a storm (auto post-mortem),
    # and the minimum wall seconds between auto-triggered bundles (a
    # sustained failure produces one black box, not a disk flood)
    DEADLINE_STORM = 3
    POSTMORTEM_MIN_INTERVAL_S = 5.0

    def __init__(self, model, *, max_seq_len: Optional[int] = None,
                 use_pallas: Optional[bool] = None, interpret: bool = False,
                 prefix_cache: Optional[bool] = None,
                 spec_tokens: Optional[int] = None,
                 drafter=None, faults: Optional[FaultInjector] = None,
                 mesh=None, tensor_parallel: Optional[int] = None,
                 replica: Optional[int] = None,
                 telemetry: Optional[Telemetry] = None,
                 host_tier=None, config=None):
        if model.state is None:
            model.compile(comp_mode=CompMode.INFERENCE)
        self.model = model
        # an explicit `config` overrides the model's: how a
        # DisaggCluster gives each role its own serving knobs (prefill
        # budget, scrape endpoint) over ONE shared model
        self.config = config if config is not None else model.config
        # observability (utils/telemetry.py, docs/observability.md):
        # per-request/per-step spans, the metrics registry, and the
        # simulator-drift calibrator. An explicit `telemetry` bus wins
        # (benches A/B on vs off over one config); else
        # FFConfig.telemetry / trace_out resolve one (off = the shared
        # disabled instance, one attribute read per site). All of it
        # is host-side: telemetry on vs off is token-identical with
        # zero recompiles (ci.sh step 1k gates <= 3% overhead).
        self.telemetry = telemetry if telemetry is not None \
            else telemetry_for(self.config)
        # telemetry track process name: a ReplicaPool re-homes each
        # replica's tracks (set_track_process) so N replicas' spans
        # don't merge onto one "serve" track in the exported trace
        self._proc = "serve"
        # what this engine's start cost, phase by phase (setup_phase;
        # boot_stats["phases"] puts the model's before them)
        self._boot_phases = boot_phases()
        with self.setup_phase("engine_init"):
            self._init(model, max_seq_len, use_pallas, interpret,
                       prefix_cache, spec_tokens, drafter, faults, mesh,
                       tensor_parallel, replica, host_tier)

    def setup_phase(self, name: str, args: Optional[dict] = None):
        """One set-up phase of this engine, through the bus's `timed`:
        kept for `boot_stats["phases"]` whether or not the bus is on,
        and on track (proc, "setup") where it is."""
        return self.telemetry.timed((self._proc, SETUP_THREAD), name,
                                    args, keep=self._boot_phases)

    def _init(self, model, max_seq_len, use_pallas, interpret,
              prefix_cache, spec_tokens, drafter, faults, mesh,
              tensor_parallel, replica, host_tier) -> None:
        # the paged-attention implementation, resolved ONCE from the
        # arguments and the backend (kernels/paged_ragged_v2.
        # resolve_paged_impl): "pallas" (Mosaic-compiled),
        # "pallas_interpret" or "jnp". Every dispatch passes the
        # resolved choice down; last_stats / boot_stats / the program
        # fingerprint report it.
        self.attn_impl = resolve_paged_impl(use_pallas, interpret)
        with self.setup_phase("read_arch"):
            self._read_arch(model)
        if max_seq_len is None:
            max_seq_len = self.max_positions
        if max_seq_len > self.max_positions:
            raise ValueError(
                f"max_seq_len {max_seq_len} exceeds the LM's served "
                f"positions ({self.max_positions})")
        self._max_seq_len = int(max_seq_len)
        # tensor-parallel sharded serving (docs/serving.md "Sharded
        # serving"): an explicit `mesh` (1-D, axis "tensor") or
        # `tensor_parallel` degree wins; otherwise FFConfig.serve_mesh
        # resolves it — "auto" closes the paper's loop for inference by
        # asking the placement search (search/serve_place.optimize_serve)
        # which degree minimizes the simulated decode step.
        self._resolve_serve_mesh(mesh, tensor_parallel, replica)
        cfg = self.config
        self.prefill_budget = int(getattr(cfg, "serve_prefill_budget", 512))
        self.prefix_cache = bool(
            getattr(cfg, "serve_prefix_cache", True)
            if prefix_cache is None else prefix_cache)
        if spec_tokens is None:
            spec_tokens = int(getattr(cfg, "serve_spec_tokens", 4)) \
                if getattr(cfg, "serve_spec_decode", True) else 0
        # what this model is not served on raises HERE, by name
        self.arch.refuse(
            tp=self.tp,
            adapters=int(getattr(cfg, "adapter_rank", 0) or 0) > 0,
            speculation=int(spec_tokens) > 0,
            prefix_cache=self.prefix_cache,
            host_tier=self.prefix_cache and bool(
                getattr(cfg, "serve_host_tier", True)) and (
                host_tier is not None
                or float(getattr(cfg, "host_tier_mb", 0.0) or 0.0) > 0))
        # the pages are those of the layers the model PAGES, at its
        # key/value heads (arch.py: kv_heads, kv_head_dim,
        # paged_layers); what a slot holds besides them is the
        # description's hybrid_spec
        self.cache_cfg = KVCacheConfig.from_ff(
            cfg, num_layers=self.arch.paged_layers,
            num_heads=self.kv_heads, head_dim=self.kv_head_dim,
            max_seq_len=max_seq_len, tensor_parallel=self.tp,
            hybrid=self.arch.hybrid_spec(self.prefill_budget),
            selector_dim=self.arch.selector_dim)
        self.cache_cfg.validate()
        self.admit_watermark = float(
            getattr(cfg, "serve_admit_watermark", 0.02))
        # robustness (docs/robustness.md): deterministic fault injection
        # (config-scoped when FFConfig.fault_spec is set), bounded
        # retry-with-backoff around jitted dispatch, per-request
        # deadlines, host-side cancellation, and the scheduler's
        # degradation ladder
        self.faults = faults if faults is not None else injector_for(cfg)
        self.trace_out = getattr(cfg, "trace_out", None)
        self._ENGINE_TRACK = (self._proc, "engine")
        self._QUEUE_TRACK = (self._proc, "queue")
        # at most ONE live ServeSession owns the scheduler/slots at a
        # time (serve/router.py keeps one open per replica; generate()
        # opens and closes its own)
        self._session: Optional["ServeSession"] = None
        # (ctx bucket) -> (predicted step seconds, per-task-class
        # breakdown) | None when the cost stack cannot price it
        self._drift_cache: Dict[int, Optional[tuple]] = {}
        self._slot_tracks: List[tuple] = []  # interned per-slot track
        # pairs, so the per-step record path never rebuilds f-strings
        self.max_retries = int(getattr(cfg, "serve_max_retries", 3))
        self.retry_backoff = float(
            getattr(cfg, "serve_retry_backoff_s", 0.02))
        # failure flight recorder (docs/observability.md): when
        # postmortem_dir is set (implies telemetry via telemetry_for),
        # the engine dumps a bounded post-mortem bundle on fault-abort,
        # deadline storm, or rung-4 rejection — rate-limited so a
        # storm produces ONE bundle, not a disk flood. dump_postmortem
        # is the explicit trigger and ignores the rate limit.
        self.postmortem_dir = getattr(cfg, "postmortem_dir", None)
        self.postmortem_events = int(
            getattr(cfg, "postmortem_events", 2048))
        self._postmortem_seq = 0
        self._postmortem_last = -float("inf")
        # requests of the most recent generate()/session run, kept for
        # explain_request(rid) (rids restart per session, so this is
        # the last run's namespace); trace ids stay globally unique
        self._last_reqs: Dict[int, Request] = {}
        self.default_deadline = float(
            getattr(cfg, "serve_request_deadline", 0.0))
        self.degrade_ladder = bool(
            getattr(cfg, "serve_degrade_ladder", True))
        self.reject_stalls = int(getattr(cfg, "serve_reject_stalls", 0))
        self._retries = 0           # engine-lifetime retried dispatches
        self._cancels: set = set()  # rids cancel() marked, swept at
        self._active: Dict[int, Request] = {}   # chunk boundaries
        # speculative decoding (serve/speculative.py): max drafted
        # tokens per sequence per step (draft lanes are chunk lanes);
        # 0 disables and the engine is bit-for-bit the
        # non-speculative one. `spec_tokens`/`drafter`
        # override the config for A/B benches and draft-LM plugins.
        self.spec_tokens = int(spec_tokens)
        self.drafter = drafter
        # KV-page storage format (serve/kv_cache.py, PR 8): lossless
        # f32 keeps the bit-exactness oracle; bf16 rounds on write
        # (exact when the engine's activations are already bf16); int8
        # quantizes on write against per-page scale arrays and the
        # ragged kernel dequantizes at read. kv_exact records whether
        # page storage preserves activation values bit-for-bit — the
        # condition for the token-identical-to-reference gate (lossy
        # formats gate bounded error + greedy parity instead,
        # tests/test_kv_quant.py).
        self.kv_dtype = self.cache_cfg.kv_dtype
        self.kv_quantized = self.cache_cfg.quantized
        self._kv_store_dtype = self.cache_cfg.storage_dtype
        self.kv_exact = (self.kv_dtype == "float32"
                         or self._kv_store_dtype == self.act_dtype)
        # tie margin of the relaxed quantized parity gate
        # (assert_token_parity): fp8's 3-bit mantissa rounds ~8x
        # coarser than int8's 127-step grid at amax scale
        self.kv_tie_margin = 0.25 if self.kv_dtype == "float8_e4m3" \
            else 0.05
        # ragged kernel v2 kv-block shape: explicit knob, else the
        # autotune-by-shape table (kernels/paged_ragged_v2.py) — sized
        # for the PER-DEVICE head count, which is what the sharded
        # kernel actually streams
        self.attn_block_kv = int(getattr(cfg, "serve_attn_block_kv", 0)) \
            or choose_block_kv(self.cache_cfg.page_size,
                               self.cache_cfg.pages_per_seq,
                               self.cache_cfg.heads_per_device,
                               self.kv_head_dim,
                               self.cache_cfg.kv_itemsize)
        # the one mixed-step geometry: every prefill-budget token plus
        # one decode lane per slot always fits
        self.mixed_width = self.prefill_budget + self.cache_cfg.max_seqs
        # what each layer's mixer kind makes of this step, decided once
        # (serve/mixers.py: which scan runs, the columns a selecting
        # model's paged calls walk, the two grid bounds with their
        # proof, the paged calls a step). The names below are read by
        # stats, tests and the benchmark: plain copies
        self.geometry = g = mixers.geometry(
            self.arch, self.cache_cfg, width=self.mixed_width,
            attn_impl=self.attn_impl, block_kv=self.attn_block_kv)
        self.scan_impl = g.scan_impl
        # the delta rule's lanes (ops/gated_delta.segmented), named in
        # the stats and the fingerprint of a model that has such a
        # layer, and of no other ({}: their keys stay what they were)
        self._delta_impl = {} if g.delta_impl is None \
            else {"delta_impl": g.delta_impl}
        self.dense_pages = g.dense_pages
        self.attn_block_pages = g.block_pages
        self.attn_max_items = g.attn_max_items
        self.window_max_items = g.window_max_items
        self.arch.kernels = g.attn_kw
        # the expert layer's gated expert is one fused kernel
        # (kernels/grouped_ffn.py, by the same arguments as the paged
        # kernel) wherever that kernel takes the step's rows and
        # weights, else three grouped matmuls ("ragged_dot"); None: no
        # expert layer
        self.expert_impl = self.arch.expert_impl(self.mixed_width)
        self.topk_cap = min(self.TOPK_CAP, self.vocab_size)
        # persistent across generate() calls: the prefix cache only
        # pays off if committed pages outlive the batch that wrote them
        self.cache = PagedKVCache(self.cache_cfg,
                                  prefix_cache=self.prefix_cache)
        # hierarchical prefix-cache tier (serve/host_tier.py): a
        # byte-budgeted host-RAM store below the HBM page pool. An
        # explicit `host_tier` (the ReplicaPool's SHARED store) wins;
        # else --host-tier-mb arms a private one. Needs the prefix
        # cache (a spilled page is reachable only through its chain
        # key). Eviction then QUEUES spills the session drains through
        # the fixed-shape export gather, and admission re-imports
        # priced host hits through the import scatter — zero new
        # compiles either way (warmup warms both programs).
        self.host_tier = None
        if self.prefix_cache and bool(
                getattr(cfg, "serve_host_tier", True)):
            if host_tier is not None:
                self.host_tier = host_tier
            elif float(getattr(cfg, "host_tier_mb", 0.0) or 0.0) > 0:
                from .host_tier import HostPageStore
                self.host_tier = HostPageStore(float(cfg.host_tier_mb))
        self.cache.host_tier = self.host_tier
        self._host_mm = None      # lazy machine model for DMA pricing
        self._host_reload_s = 0.0  # priced DMA seconds, pending step
        self._host_reload_stats = {"reload_events": 0,
                                   "reload_pages": 0,
                                   "spilled_pages": 0,
                                   "recompute_chosen": 0,
                                   "reload_priced_s": 0.0}
        self.pool: Optional[KVPool] = None   # lazy: _device_pool()
        # the newest mixed dispatch's `greedy`, on the device: the next
        # dispatch's token source (_dispatch_mixed)
        self._greedy = None
        # multi-tenant LoRA adapter pool (serve/adapters.py): fixed
        # rank-padded HBM slabs managed like the KV pool, slot 0 the
        # reserved all-zero base slab so base and adapted lanes mix in
        # the ONE mixed program. Armed by adapter_rank > 0; the slabs
        # flow READ-ONLY through the mixed step (gathered per lane,
        # never donated) and tenant loads run through one jitted
        # donating scatter ("adapter" in the compile accounting).
        self.adapters = None
        self.adapter_cfg = None
        self._adapter_slabs = None     # device pytree, lazy like pages
        self._adapter_specs = None     # PartitionSpec dict (tp > 1)
        self._adapter_shardings = None
        if int(getattr(cfg, "adapter_rank", 0) or 0) > 0:
            from .adapters import AdapterConfig, AdapterPool
            self.adapter_cfg = AdapterConfig.from_ff(
                cfg, num_layers=self.num_layers, hidden=self.hidden,
                num_heads=self.num_heads, head_dim=self.head_dim,
                ff_dim=self._ff_pad,
                act_itemsize=int(self.act_dtype.itemsize),
                tensor_parallel=self.tp)
            self.adapters = AdapterPool(self.adapter_cfg)
            if self.tp > 1:
                from jax.sharding import NamedSharding
                from jax.sharding import PartitionSpec as P
                # B factors shard where their output dim does (heads /
                # padded ff), A factors contracting a sharded dim
                # (wo's heads, ff2's ff) shard on it; the rank-side
                # rest replicates — per-device deltas are then local
                # partials the existing psums complete exactly
                self._adapter_specs = {
                    "a_qkv": P(),
                    "b_qkv": P(None, None, None, None, TENSOR, None),
                    "a_wo": P(None, None, TENSOR, None, None),
                    "b_wo": P(),
                    "a_ff1": P(),
                    "b_ff1": P(None, None, None, TENSOR),
                    "a_ff2": P(None, None, TENSOR, None),
                    "b_ff2": P(),
                    "scale": P(),
                }
                self._adapter_shardings = {
                    k: NamedSharding(self.tp_mesh, s)
                    for k, s in self._adapter_specs.items()}
        # prompt-length buckets (generate_reference): powers of two from one page up to the serveable length. The
        # page-table ceiling rounds UP to whole pages, but a bucket
        # wider than max_seq_len would forward positions the model
        # never learned (and no admissible request can need)
        cap = min(self.cache_cfg.pages_per_seq * self.cache_cfg.page_size,
                  self.cache_cfg.max_seq_len)
        b = max(self.cache_cfg.page_size, 16)
        self.buckets = []
        while b < cap:
            self.buckets.append(b)
            b *= 2
        self.buckets.append(cap)
        # the mixed step: single-device, or shard_map'd over the serve
        # mesh (same lane contract, same donation) — ONE program
        # either way, keyed by the pool's pytree (quantized pools carry
        # their scale arrays through the same step, donated alongside)
        args = {}
        with self.setup_phase("shard_params", args):
            if self.tp > 1:
                self._step_params, self._param_specs = \
                    self._shard_params()
            else:
                # a placed replica holds its own copy of the weights on
                # its chip; an unplaced engine reads the model's arrays
                # in place
                self._step_params = self.params if self._home is None \
                    else jax.device_put(self.params, self._home)
            args["bytes"] = _tree_bytes(self._step_params)
        self._mixed_jit = jax.jit(self._mixed_impl, donate_argnums=(1,))
        self._forward_jit = jax.jit(self._forward_logits)  # naive reference
        if self.adapters is not None:
            # the on-demand tenant load: donate-in-place row write into
            # the slabs, ONE program for every (slot, tenant) — the
            # admission stall is a dispatch, never a recompile
            self._adapter_load_jit = jax.jit(
                self._adapter_load_impl, donate_argnums=(0,),
                out_shardings=self._adapter_shardings)
        # disaggregated page handoff (serve/disagg.py): fixed-shape
        # gather/scatter programs moving whole page rows (values +
        # scale rows on quantized pools) between this engine's pool
        # and the host. The page-index vector is padded to
        # pages_per_seq with 0 — the sink-page convention — so ONE
        # program geometry serves every shipment size and the
        # zero-recompile contract extends to handoff traffic. Import
        # donates the pool exactly like the mixed step.
        self._export_jit = jax.jit(self._export_impl)
        self._import_jit = jax.jit(self._import_impl, donate_argnums=(0,))
        # per-function compile accounting, owned by the ProgramRegistry
        # (core/programs.py): every serving dispatch resolves through
        # registry.call, which AOT-compiles on a new argument signature
        # and counts EXACTLY — no monitoring-snapshot coverage gap on
        # compiles inside warmup_handoff / adapter load — and which
        # restores serialized executables from --program-cache-dir so a
        # cold replica boots warm (zero compiles). `_compiles` stays
        # the registry's live per-family dict (test/bench API compat);
        # `_events_ok` is always True now that counting is exact.
        self.programs = ProgramRegistry(
            self._program_fingerprint(),
            cache_dir=getattr(cfg, "program_cache_dir", None),
            phase=self.setup_phase)
        for fam in ("mixed", "adapter", "export", "import"):
            self.programs.register(fam)
        self.programs_restored = self.programs.load_warm()
        self._events_ok = True
        self._compiles = self.programs._compiles
        self.boot_stats: Optional[dict] = None
        self.last_stats: Optional[dict] = None
        # live scrape endpoint (--metrics-port, docs/observability.md):
        # /metrics serves the engine-lifetime registry as Prometheus
        # text, /healthz liveness — the autoscaler's poll target.
        # Started LAST (a construction failure above must not leak a
        # bound port/thread), stopped by close(); scrapes read the
        # registry from the server thread, never touching the serving
        # hot path.
        self.metrics_server = None
        mport = getattr(cfg, "metrics_port", None)
        if mport is not None:
            from ..utils.telemetry import MetricsServer
            self.metrics_server = MetricsServer(
                self.telemetry.to_prometheus, port=int(mport),
                host=str(getattr(cfg, "metrics_host", "127.0.0.1")))

    def _call_counted(self, name, fn, *args):
        attempt = 0
        while True:
            try:
                # fault-injection site: serve.mixed (serve.export,
                # ...), fired at the dispatch boundary (BEFORE the
                # jitted call, so donated buffers are untouched when
                # an injected fault raises)
                self.faults.fire(f"serve.{name}")
                # the registry resolves (family, argument signature) to
                # a compiled executable: hit -> dispatch (possibly an
                # executable deserialized at boot — the warm path),
                # miss -> AOT lower().compile(), timed and counted
                out = self.programs.call(name, fn, *args)
                break
            except TransientError:
                # bounded retry-with-backoff: transient dispatch faults
                # (injected chaos, a flaky link) are absorbed here
                # instead of failing the batch. Only retry while
                # the donated page arrays are still live — a dispatch
                # that consumed them before dying cannot be redone.
                attempt += 1
                if attempt > self.max_retries or any(
                        a.is_deleted() for a in jax.tree.leaves(args)
                        if hasattr(a, "is_deleted")):
                    raise
                self._retries += 1
                if self.telemetry.enabled:
                    self.telemetry.instant(
                        self._ENGINE_TRACK, "retry",
                        args={"site": f"serve.{name}",
                              "attempt": attempt})
                if self.retry_backoff:
                    tb = time.perf_counter()
                    time.sleep(self.retry_backoff * (2 ** (attempt - 1)))
                    if self.telemetry.enabled:
                        # the backoff is dead time EVERY request in
                        # this step pays: a complete span (not an
                        # instant) so explain_request can carve it out
                        # of the covering chunk spans as "retry"
                        self.telemetry.span(
                            self._ENGINE_TRACK, "retry_backoff", tb,
                            time.perf_counter(),
                            args={"site": f"serve.{name}",
                                  "attempt": attempt})
        return out

    @property
    def head_rows(self) -> int:
        """The rows the step's head, argmax and top-k run over: one a
        sequence that can emit (a sequence has at most one chunk a
        step), plus its drafts under speculation; never more than the
        step's lanes, where the gather is the identity."""
        return min(self.mixed_width,
                   self.cache_cfg.max_seqs * (1 + self.spec_tokens))

    def _program_fingerprint(self) -> Dict:
        """The cache identity of this engine's program set: everything
        that shapes or numbers a serving executable. Two engines with
        equal fingerprints compile bit-identical programs (the AOT
        snapshot in --program-cache-dir is keyed on its hash); flipping
        ANY folded field — kv dtype, adapter rank, tp degree, the jax
        version — must miss the cache (tests/test_programs.py pins
        each)."""
        c = self.cache_cfg
        ac = self.adapter_cfg
        return {
            "kind": "serve",
            "arch": self.arch.kind,
            "experts": (self.arch.experts, self.arch.experts_per_token),
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "devices": jax.device_count(),
            "num_layers": self.num_layers,
            "hidden": self.hidden,
            "num_heads": self.num_heads,
            "head_dim": self.head_dim,
            "kv_heads": (self.kv_heads, self.kv_head_dim,
                         self.arch.paged_layers),
            "hybrid": None if self.cache_cfg.hybrid is None
            else dataclasses.astuple(self.cache_cfg.hybrid),
            "ff_pad": self._ff_pad,
            "vocab": self.vocab_size,
            "max_positions": self.max_positions,
            "layer_norm": self.layer_norm,
            "act_dtype": str(self.act_dtype),
            "max_seq_len": self._max_seq_len,
            "prefill_budget": self.prefill_budget,
            "mixed_width": self.mixed_width,
            "head_rows": self.head_rows,
            "topk_cap": self.topk_cap,
            "buckets": tuple(self.buckets),
            "kv_dtype": self.kv_dtype,
            "kv_store_dtype": str(self._kv_store_dtype),
            "page_size": c.page_size,
            "pages_per_seq": c.pages_per_seq,
            "num_pages": c.num_pages,
            "max_seqs": c.max_seqs,
            "attn_block_kv": self.attn_block_kv,
            "adapter_rank": 0 if ac is None else ac.rank,
            "adapter_slots": 0 if ac is None else ac.num_slots,
            "tp": self.tp,
            # an executable runs only on the devices it was compiled
            # for: replicas on different chips keep separate stores
            "device_ids": tuple(int(d.id) for d in self.devices),
            "attn_impl": self.attn_impl,
            "scan_impl": self.scan_impl,
            "expert_impl": self.expert_impl,
            **self._delta_impl,
        }

    # ---------------- model introspection -----------------------------
    def _read_arch(self, model) -> None:
        """Ask serve/arch.py what this model is; the engine keeps the
        description (`self.arch`: the block's math) and copies the
        dimensions its own geometry is built from."""
        self.arch = a = describe(model)
        self.vocab_size = a.vocab_size
        self.max_positions = a.max_positions
        self.layer_norm = a.layer_norm
        self.num_layers = a.num_layers
        self.num_heads = a.num_heads
        self.head_dim = a.head_dim
        self.kv_heads = a.kv_heads          # of the pages
        self.kv_head_dim = a.kv_head_dim
        self.hidden = a.hidden
        self.ln_eps = a.ln_eps
        self.act_dtype = a.act_dtype
        self.ff_dim = a.ff_dim
        self.params = model.state.params  # live references, not copies

    # ---------------- tensor-parallel sharding -------------------------
    def _resolve_serve_mesh(self, mesh, tensor_parallel,
                            replica=None) -> None:
        """Resolve (tp, tp_mesh, devices) from the explicit args or
        FFConfig.serve_mesh ('' = single device, 'N' = degree N,
        'auto' = the placement search picks). `replica` (a pool's
        index for this engine) places it on chips
        [replica*tp, (replica+1)*tp) — parallel/mesh.replica_devices —
        instead of the first tp; None keeps a tp=1 engine's arrays
        uncommitted on the default device."""
        cfg = self.config
        self.serve_placement = None  # set by the 'auto' path below
        if mesh is None and tensor_parallel is None:
            sm = str(getattr(cfg, "serve_mesh", "") or "").strip()
            if sm == "auto":
                from ..search.serve_place import optimize_serve
                place = optimize_serve(self.serve_arch(),
                                       len(jax.devices()), config=cfg)
                self.serve_placement = place
                tensor_parallel = place.tensor_parallel
            elif sm:
                tensor_parallel = int(sm)
        self.tp = 1
        self.tp_mesh = None
        if mesh is not None:
            if TENSOR not in mesh.shape:
                raise ValueError(
                    f"serve mesh needs a {TENSOR!r} axis, got "
                    f"{dict(mesh.shape)}")
            self.tp = int(mesh.shape[TENSOR])
            self.tp_mesh = mesh if self.tp > 1 else None
        elif tensor_parallel is not None and int(tensor_parallel) > 1:
            self.tp = int(tensor_parallel)
            self.tp_mesh = serve_tensor_mesh(
                self.tp, replica_devices(replica or 0, self.tp))
        # where this engine's programs run, and (placed tp=1 replicas
        # only) the sharding its weights, pools and host-built step
        # inputs commit to
        self._home = None
        if self.tp_mesh is not None:
            self.devices = tuple(self.tp_mesh.devices.flat)
        elif replica is not None:
            from jax.sharding import SingleDeviceSharding
            self.devices = replica_devices(replica, 1)
            self._home = SingleDeviceSharding(self.devices[0])
        else:
            self.devices = (jax.devices()[0],)
        if self.tp > 1 and self.num_heads % self.tp != 0:
            raise ValueError(
                f"sharded serving needs num_heads ({self.num_heads}) "
                f"divisible by the tensor degree ({self.tp})")
        # ff/vocab need not divide: their shards PAD (zero ff columns
        # contribute exact zeros; pad vocab columns carry a -1e30 bias
        # so they never win argmax) — exactness is unaffected
        self._ff_pad = -(-self.ff_dim // self.tp) * self.tp
        self._vocab_pad = -(-self.vocab_size // self.tp) * self.tp

    def serve_arch(self, context: Optional[int] = None):
        """The ServeArch the placement search prices for this engine's
        model + serving knobs (search/cost_model.serve_step_tasks):
        decode lanes = the slot reserve, prefill lanes = the budget,
        steady-state context defaulting to 3/4 of the serveable length,
        KV traffic at the configured page format's itemsize."""
        from ..search.cost_model import ServeArch
        if self.arch.experts:
            raise NotImplementedError(
                f"serve placement prices a dense feed-forward: the "
                f"{self.arch.kind} expert layer ({self.arch.experts} "
                f"experts, {self.arch.experts_per_token} a token) has no "
                f"ServeArch yet, so serve_mesh='auto' cannot place it; "
                f"serve it on one device")
        if self.arch.hybrid_spec(1) is not None:     # whatever the chunk
            raise NotImplementedError(
                f"serve placement prices attention layers over pages: "
                f"the {self.arch.kind} state-space, window and cross "
                f"layers have no ServeArch yet, so serve_mesh='auto' "
                f"cannot place them; serve it on one device")
        cfg = self.config
        kv_name = str(getattr(cfg, "kv_dtype", "float32"))
        from .kv_cache import QUANTIZED_KV_DTYPES
        # adapter-pool pricing terms: the armed engine's true pool
        # geometry, or (on the serve_mesh=auto path, which prices the
        # arch BEFORE the pool exists) an unsharded estimate from the
        # same from_ff sizing — the search sees the residency cost it
        # is trading tensor degree against
        acfg = getattr(self, "adapter_cfg", None)
        if acfg is None and int(getattr(cfg, "adapter_rank", 0) or 0) > 0:
            from .adapters import AdapterConfig
            acfg = AdapterConfig.from_ff(
                cfg, num_layers=self.num_layers, hidden=self.hidden,
                num_heads=self.num_heads, head_dim=self.head_dim,
                ff_dim=self.ff_dim,
                act_itemsize=int(self.act_dtype.itemsize))
        return ServeArch(
            num_layers=self.num_layers, hidden=self.hidden,
            num_heads=self.num_heads, head_dim=self.head_dim,
            ff_dim=self.ff_dim, vocab=self.vocab_size,
            decode_lanes=int(getattr(cfg, "serve_max_seqs", 8)),
            prefill_lanes=int(getattr(cfg, "serve_prefill_budget", 512)),
            context=int(context if context is not None
                        else max(1, self._max_seq_len * 3 // 4)),
            kv_dtype=kv_name,
            kv_itemsize=float(kv_storage_dtype(kv_name).itemsize),
            kv_scales=kv_name in QUANTIZED_KV_DTYPES,
            act_itemsize=float(self.act_dtype.itemsize),
            act_dtype=str(self.act_dtype.name),
            adapter_rank=acfg.rank if acfg is not None else 0,
            adapter_slots=acfg.num_slots if acfg is not None else 0)

    def _shard_params(self):
        """Shard (and where needed pad) the LM parameters over the
        serve mesh, returning (params, PartitionSpec pytree):

          wq/wk/wv (E, H, D)  -> heads column-parallel
          wo       (H, D, E)  -> heads row-parallel (psum after)
          ff1      (E, F)     -> column-parallel (+ bias shard)
          ff2      (F, E)     -> row-parallel (psum before bias)
          lm_head  (E, V)     -> vocab column-parallel (all-gather at
                                 the logits; pad columns biased -inf)
          tok_embed (V, E)    -> vocab row-parallel (masked local
                                 gather + exact psum — one device owns
                                 each row, the rest contribute 0.0)
          everything else     -> replicated (LNs, pos_embed, biases)

        The originals in self.params stay untouched — the reference
        paths (generate_reference, assert_token_parity's margin
        forward) keep running single-device on them."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        mesh = self.tp_mesh

        def pad_to(a, axis, size, value=0.0):
            extra = size - a.shape[axis]
            if extra <= 0:
                return a
            widths = [(0, 0)] * a.ndim
            widths[axis] = (0, extra)
            return jnp.pad(a, widths, constant_values=value)

        def put(a, *spec):
            return jax.device_put(a, NamedSharding(mesh, P(*spec)))

        out: Dict[str, dict] = {}
        specs: Dict[str, dict] = {}
        for name, p in self.params.items():
            o, s = {}, {}
            for key, arr in p.items():
                arr = jnp.asarray(arr)
                spec = ()
                if name == "tok_embed" and key == "kernel":
                    arr = pad_to(arr, 0, self._vocab_pad)
                    spec = (TENSOR,)
                elif name.endswith("_attn") and key in ("wq", "wk",
                                                        "wv"):
                    spec = (None, TENSOR)
                elif name.endswith("_attn") and key == "wo":
                    spec = (TENSOR,)
                elif name.endswith("_ff1") and key == "kernel":
                    arr = pad_to(arr, 1, self._ff_pad)
                    spec = (None, TENSOR)
                elif name.endswith("_ff1") and key == "bias":
                    arr = pad_to(arr, 0, self._ff_pad)
                    spec = (TENSOR,)
                elif name.endswith("_ff2") and key == "kernel":
                    arr = pad_to(arr, 0, self._ff_pad)
                    spec = (TENSOR,)
                elif name == "lm_head" and key == "kernel":
                    arr = pad_to(arr, 1, self._vocab_pad)
                    spec = (None, TENSOR)
                elif name == "lm_head" and key == "bias":
                    arr = pad_to(arr, 0, self._vocab_pad,
                                 value=_PAD_LOGIT_BIAS)
                    spec = (TENSOR,)
                o[key] = put(arr, *spec)
                s[key] = P(*spec)
            if name == "lm_head" and "bias" not in p \
                    and self._vocab_pad > self.vocab_size:
                # padded vocab columns must never win argmax:
                # synthesize a bias (+0.0 on real columns is exact)
                b = jnp.zeros((self._vocab_pad,), self.act_dtype)
                b = b.at[self.vocab_size:].set(_PAD_LOGIT_BIAS)
                o["bias"] = put(b, TENSOR)
                s["bias"] = P(TENSOR)
            out[name], specs[name] = o, s
        return out, specs

    def _sharding_stats(self) -> Optional[dict]:
        """The last_stats/serve_report sharding block: mesh shape,
        heads per device, per-device KV pool bytes, and the analytic
        per-step collective payload (2 all-reduces of the lane
        activations per layer + the embedding psum + the final logits
        all-gather, of the head's rows)."""
        if self.tp <= 1:
            return None
        c = self.cache_cfg
        T = self.mixed_width
        act = int(self.act_dtype.itemsize)
        coll = ((2 * self.num_layers + 1) * T * self.hidden * act
                + self.head_rows * self._vocab_pad * act)
        return {
            "mesh": {TENSOR: self.tp},
            "tensor_parallel": self.tp,
            "heads_per_device": self.num_heads // self.tp,
            "kv_pool_device_bytes": int(c.pool_device_bytes),
            "collective_bytes_per_step": int(coll),
        }

    def mixed_step_cost_analysis(self) -> Optional[dict]:
        """XLA's own cost analysis of the compiled mixed program — the
        PER-DEVICE program under sharding, so serve_bench's sharded
        FLOPs-per-device gate reads a measured number, not the analytic
        formula it is checking. Lowers the engine's mixed step at its
        fixed geometry over the resident pool's ShapeDtypeStructs
        (AOT — nothing executes, and no duplicate KV pool is
        materialized next to the resident one) and returns the
        backend's dict ({'flops': ...,} etc.), or None where the
        backend doesn't implement cost analysis. The AOT compile is
        out-of-band of `_call_counted`'s per-program snapshots, but
        call it outside timed/recompile-gated regions anyway."""
        c = self.cache_cfg
        T = self.mixed_width
        pool = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            self._device_pool())
        i32 = jnp.int32
        lane = jnp.zeros((T,), i32)
        args = (self._step_params, pool, lane, lane, lane, lane,
                jnp.zeros((c.max_seqs, c.pages_per_seq), i32),
                lane, lane, jnp.zeros((self.head_rows,), i32), lane,
                jnp.zeros((self.head_rows,), i32))
        if self.adapters is not None:
            slabs = {
                key: jax.ShapeDtypeStruct(
                    shape,
                    jnp.float32 if key == "scale" else self.act_dtype,
                    sharding=(self._adapter_shardings or {}).get(key))
                for key, shape in self._adapter_slab_shapes().items()}
            args += (lane, slabs)
        else:
            args += (None, None)
        try:
            ca = self._mixed_jit.lower(*args).compile().cost_analysis()
        except (NotImplementedError, jax.errors.JaxRuntimeError):
            return None
        return dict(ca) if ca else None

    # ---------------- sharded block math (inside shard_map) ------------
    def _embed_tp(self, params, tokens, positions, axis):
        """Vocab-row-sharded token embedding: each device gathers the
        rows it owns and contributes exact 0.0 for the rest, so the
        psum reproduces the unsharded rows BIT-identically (x + 0.0 is
        exact — the one cross-device sum in the program with no
        rounding cost). The same OOB discipline as ops/embedding's
        flat slot-offset gather (_slot_gather): local indices clamp
        in-range so no lane ever reads a NaN 'fill' row, and the mask
        zeroes anything the clamp aliased. pos_embed is replicated
        (positions are tiny next to vocab)."""
        kern = params["tok_embed"]["kernel"]          # (Vp/t, E) local
        rows = kern.shape[0]
        lo = jax.lax.axis_index(axis) * rows
        idx = tokens - lo
        te = jnp.take(kern, jnp.clip(idx, 0, rows - 1), axis=0)
        te = jnp.where(((idx >= 0) & (idx < rows))[:, None], te, 0)
        te = jax.lax.psum(te, axis)
        pe = jnp.take(params["pos_embed"]["kernel"], positions, axis=0,
                      mode="clip")
        return (te + pe).astype(self.act_dtype)

    def _head_tp(self, params, x, axis):
        """Vocab-column-sharded head: each device computes its V/t
        logit columns (full contraction over E — no partial sums) and
        ONE all-gather assembles the (R, vocab_pad) logits, replicated,
        for the argmax/top-k tail (`x` is the rows the step's head
        takes, the emitting lanes', gathered BEFORE the product). This
        is the program's only all-gather — the 'sharded vocab, gather
        only at the final logits' contract."""
        local = _dense(params["lm_head"],
                       self.arch.final_norm(params, x))  # (R, Vp/t)
        return jax.lax.all_gather(local, axis, axis=1, tiled=True)

    # ---------------- full-sequence forward (the reference) -----------
    def _forward_logits(self, params, tokens, length):
        """Causal no-cache forward over (1, S) padded tokens; returns
        the logits of position length-1 — the naive greedy-decode
        reference generate() is tested against."""
        s = tokens.shape[1]
        positions = jnp.arange(s, dtype=jnp.int32)[None, :]
        arch = self.arch
        if arch.forward_logits is not None:
            # a model of other mixers than attention: its op graph's
            # own full-sequence forward is the oracle
            return jnp.take(arch.forward_logits(params, tokens),
                            length - 1, axis=0)
        x = arch.embed(params, tokens, positions)         # (1, S, E)
        scale = 1.0 / np.sqrt(self.head_dim)
        causal = jnp.tril(jnp.ones((s, s), dtype=bool))
        for i in range(self.num_layers):
            q, k, v = arch.qkv(params, i, arch.norm1(params, i, x),
                               positions)                 # (1, S, H, D)
            logits = jnp.einsum("bihd,bjhd->bhij", q, k,
                                preferred_element_type=jnp.float32) * scale
            logits = jnp.where(causal, logits, -jnp.inf)
            # probs STAY f32 through the p.v product — the paged
            # kernels' convention (p stays f32 and v upcasts) — so a
            # bf16 engine's reference forward and
            # its paged path diverge only at f32 epsilon, not at bf16
            # prob-rounding scale (which flips greedy argmaxes). For
            # f32 engines this is bit-identical to rounding probs.
            probs = jax.nn.softmax(logits, axis=-1)
            o = jnp.einsum("bhij,bjhd->bihd", probs,
                           v.astype(jnp.float32),
                           preferred_element_type=jnp.float32
                           ).astype(x.dtype)
            x = arch.attn_out(params, i, o, x)
            x, _ = arch.ffn(params, i, x)
        logits = arch.head(params, x)                     # (1, S, V)
        return jnp.take(logits[0], length - 1, axis=0)    # (V,)

    # ---------------- the mixed step (chunked prefill + decode) --------
    def _mixed_impl(self, params, pool, tokens, positions, write_pages,
                    write_offs, page_tables, lane_slots, lane_lens,
                    head_lanes, token_src, prev_greedy,
                    lane_adapters=None, adapters=None):
        """ONE serving step over `mixed_width` LANES. Per lane (all
        (T,) int32, HOST-built): the token to embed, its position, the
        physical (page, offset) its K/V lands in (inactive lanes aim at
        the sink page 0), the page-table row it reads
        (lane_slots -> page_tables (max_seqs, pages_per_seq)) and its
        visible length (position + 1; inactive lanes clamp to 1 so the
        masked softmax stays NaN-free). All lanes' K/V is written to
        `pool` (donated) per layer BEFORE attention, so chunk tokens of
        one sequence see each other causally and decode lanes see every
        prefix page — including pages another request's chunk computes
        in this very step (the intra-step prefix-sharing contract,
        serve/scheduler.py). Inactive lanes compute garbage the host
        never reads. The head runs over R rows, not over the T lanes:
        the last layer's output at `head_lanes` ((R,) int32, HOST-built:
        the lanes whose logits the host reads, padded with lane 0; R is
        `head_rows` in the engine's own step). Returns (greedy (R,),
        top-k values (R, K), top-k ids (R, K)[, expert counts], pool) —
        the static top-k head feeds host-side seeded sampling without
        shipping (R, vocab) logits. A lane whose token the host does
        not have yet takes it on the device: `token_src` ((T,) int32,
        HOST-built) is the row of `prev_greedy` (the previous step's
        `greedy`, a device array never fetched first) that holds it,
        -1 for the host's token (ServeSession runs one step ahead of
        the host).

        With a serve mesh the same body runs shard_map'd over it: each
        device on its H/t heads of the params and the pool (tp_axis
        threads the psums / all-gather; scales shard on the same head
        axis and per-row quantization is per-head, so each device's
        stored rows are BIT-identical to the unsharded engine's rows
        for those heads). check_vma off: the replicated outputs come
        out of collectives, which the static replication checker
        cannot always see through."""
        def step(*args, tp_axis=None):
            out, pool = self._mixed_body(*args, tp_axis=tp_axis)
            return (*out, pool)

        args = (params, pool, tokens, positions, write_pages, write_offs,
                page_tables, lane_slots, lane_lens, head_lanes, token_src,
                prev_greedy, lane_adapters, adapters)
        if self.tp_mesh is None:
            return step(*args)
        import functools

        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        # params per _shard_params, the pool on the head axis, every
        # host-built lane array replicated (the adapter lanes too; the
        # slabs per _adapter_specs — unarmed engines pass None, an
        # empty pytree any prefix spec matches), the emitted token
        # streams replicated (psum/all-gather results are), and so the
        # previous step's that comes back in
        rep, pool_spec = P(), KVPool.specs(TENSOR)
        ins = (self._param_specs, pool_spec) + (rep,) * 11 + (
            self._adapter_specs if self._adapter_specs is not None
            else rep,)
        return shard_map(functools.partial(step, tp_axis=TENSOR),
                         mesh=self.tp_mesh, in_specs=ins,
                         out_specs=(rep, rep, rep, pool_spec),
                         check_vma=False)(*args)

    @jax.named_scope("serve_step")
    def _mixed_body(self, params, pool, tokens, positions, write_pages,
                    write_offs, page_tables, lane_slots, lane_lens,
                    head_lanes, token_src, prev_greedy,
                    lane_adapters=None, adapters=None, tp_axis=None):
        """The mixed step's body -> (outputs, pool). Every layer
        writes its lanes' K/V to the pool (KVPool.write: the storage
        format's cast or quantization) BEFORE any lane attends, so
        what a lane reads back this very step is already the stored
        value — quantized content is therefore invariant to chunk
        boundaries, preemption replays, and speculative rollbacks
        (every token's row quantizes independently).

        `tp_axis` runs the SAME body per device inside shard_map over
        the serve mesh: head-sharded params/pages make attention and
        quantization per-head-identical (each head's rows are the
        unsharded bits), the two per-layer psums complete the
        row-parallel projections, and the head all-gathers its vocab
        shards. Exactly one program geometry either way."""
        # named scopes (docs/observability.md "Device scopes"): metadata
        # only — under the root `serve_step` they name each device
        # operation's phase and layer in a profiler trace and change no
        # fusion. The f32 masters are cast to the activation dtype
        # where each weight is used, so every cast falls in the scope
        # of the phase that does it.
        scope = jax.named_scope
        with scope("embed"):
            # the token a step still in flight will emit: read where it
            # is, so this step need not wait for the host's copy
            tokens = jnp.where(
                token_src >= 0,
                jnp.take(prev_greedy, jnp.maximum(token_src, 0)), tokens)
            x = (self._embed_tp(params, tokens, positions, tp_axis)
                 if tp_axis else
                 self.arch.embed(params, tokens, positions))  # (T, E)
        # multi-tenant adapters (serve/adapters.py): ONE gather pulls
        # each lane's whole (A, B) stack — slab (S, L, ...) rows by
        # the lane's slot index — so the per-layer loop just slices.
        # Slot 0 is the reserved zero slab: base-model and inactive
        # lanes add exactly 0.0. Under shard_map the gather runs on
        # each device's local slab shard (replicated lane indices).
        ad = ad_s = None
        if adapters is not None:
            with scope("adapters"):
                ad = {key: jnp.take(arr, lane_adapters, axis=0)
                      for key, arr in adapters.items() if key != "scale"}
                ad_s = jnp.take(adapters["scale"], lane_adapters, axis=0)
        # what the layers share, made of the lane arrays once: the work
        # lists, the runs, the rings (serve/mixers.py)
        lanes = mixers.step_lanes(self.geometry, positions, write_pages,
                                  write_offs, page_tables, lane_slots,
                                  lane_lens)
        # what the description's memory layer hands the layers after it
        memory = None
        expert_counts, selected = [], []
        for i in range(self.num_layers):
            with scope(f"layer{i}"):
                x, pool, memory, counts, walked = self._mixed_layer(
                    params, i, x, lanes, pool, memory,
                    None if ad is None else
                    {key: arr[:, i] for key, arr in ad.items()},
                    ad_s, tp_axis)
                # a layer whose feed-forward is dense routes nothing
                if counts is not None:
                    expert_counts.append(counts)
                selected += walked
        with scope("head"):
            # only the lanes that emit have logits anyone reads: the
            # head, the argmax and the sort take their rows, not the
            # step's width
            x = jnp.take(x, head_lanes, axis=0)              # (R, E)
            logits = (self._head_tp(params, x, tp_axis) if tp_axis
                      else self.arch.head(params, x))        # (R, V[pad])
        with scope("sample"):
            topv, topi = jax.lax.top_k(logits, self.topk_cap)
            out = (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                   topv.astype(jnp.float32), topi.astype(jnp.int32))
        if self.arch.experts:
            # over the layers that ROUTE: all of them, or those after a
            # model's leading dense layers
            out += (jnp.stack(expert_counts),)           # (layers, E)
        if selected:
            # what the SPARSE layers' selections walked, summed
            out += (sum(selected),)                      # (2,)
        return out, pool

    def _mixed_layer(self, params, i, x, lanes, pool, memory, la, ad_s,
                     tp_axis):
        """Layer `i` of the mixed step -> (x, pool, memory, the layer's
        live slots per expert or None, what only the device counts of
        the mixer's work — the body's fourth value in a list, [] of a
        body without one): `ln`, then the body of the
        layer's mixer kind (serve/mixers.py BODIES, under its own
        scopes), then the description's feed-forward under its scopes
        (`ffn`, or `router`, `moe_dispatch`, `experts`, `moe_combine`,
        with `shared_experts` where there are some). A PARALLEL block
        (arch.parallel_block) has the one norm: the feed-forward reads
        `h` too, both branches come back alone and `residual` adds them
        to x once; a POST-NORM block (arch.post_norm) has no `ln`: both
        branches come back alone too, and each is normed under
        `post_norm` and added where it stood. `la` is the lanes' adapter
        rows of this layer (None: no adapters), `ad_s` their scales;
        `memory` what the memory layer's body returned for the layers
        after it."""
        scope = jax.named_scope
        arch = self.arch
        with scope("ln"):
            h = arch.norm1(params, i, x)
        # x + the mixer's branch or, in a parallel block, the branch
        # alone
        a, pool, memory, *walked = mixers.BODIES[arch.mixer(i)](
            self.geometry, params, i, x, h, lanes, pool, memory,
            None if la is None else (la, ad_s), tp_axis)
        if arch.parallel_block:
            f, counts = arch.ffn(params, i, x, h=h, live=lanes.ffn_live,
                                 psum_axis=tp_axis)
            with scope("residual"):
                return x + (a + f), pool, memory, counts, walked
        if arch.post_norm:
            # the norm stands AFTER a sub-layer: each branch comes back
            # alone and is normed before it is added
            with scope("post_norm"):
                x = x + arch.branch_norm(params, i, 1, a)
            f, counts = arch.ffn(params, i, x, live=lanes.ffn_live,
                                 psum_axis=tp_axis)
            with scope("post_norm"):
                return (x + arch.branch_norm(params, i, 2, f), pool,
                        memory, counts, walked)
        x, counts = arch.ffn(
            params, i, a, live=lanes.ffn_live, psum_axis=tp_axis,
            lora=None if la is None else
            (la["a_ff1"], la["b_ff1"], la["a_ff2"], la["b_ff2"], ad_s))
        return x, pool, memory, counts, walked

    # ---------------- disaggregated page handoff -----------------------
    # Device half of the prefill->decode transfer (serve/disagg.py;
    # host bookkeeping in PagedKVCache.export_pages/import_pages).
    # Both directions move whole pages of every layer (KVPool.rows /
    # with_rows: the stored values, and the f32 scales on quantized
    # pools, so quantized content crosses the link bit-exactly and
    # dequantizes identically on the far side) through ONE fixed-shape
    # program each, shard_map'd over the serve mesh where there is one
    # (pool AND rows on the head axis, the index vector replicated):
    # the page-index vector pads to pages_per_seq with the sink page
    # 0, exactly the padding convention of the mixed step's write
    # lanes.

    def _over_mesh(self, fn, *arg_specs):
        """A handoff body `fn(pool, ...) -> pool`: itself on one
        device, shard_map'd over the serve mesh where there is one."""
        if self.tp_mesh is None:
            return fn
        from jax import shard_map
        spec = KVPool.specs(TENSOR)
        return shard_map(fn, mesh=self.tp_mesh,
                         in_specs=(spec,) + arg_specs, out_specs=spec,
                         check_vma=False)

    def _export_impl(self, pool, idx):
        """Gather pages: idx (pages_per_seq,) int32, padding entries
        aim at the sink (their rows ship as garbage the importer never
        addresses)."""
        from jax.sharding import PartitionSpec as P
        return self._over_mesh(KVPool.rows, P())(pool, idx)

    def _import_impl(self, pool, rows, idx):
        """Scatter pages into the (donated) pool. Padding entries
        write their (zero) rows into the sink page — harmless by the
        sink convention (reads are masked by seq_lens)."""
        from jax.sharding import PartitionSpec as P
        return self._over_mesh(KVPool.with_rows, P(), KVPool.specs(TENSOR))(
            pool, idx, rows)

    def _pad_idx(self, pages: Sequence[int]) -> np.ndarray:
        c = self.cache_cfg
        if len(pages) > c.pages_per_seq:
            raise ValueError(
                f"shipment of {len(pages)} pages exceeds this pool's "
                f"page-table ceiling ({c.pages_per_seq})")
        idx = np.zeros((c.pages_per_seq,), np.int32)
        idx[:len(pages)] = pages
        return idx

    def export_kv(self, slot: int, tokens: Sequence[int],
                  stream_id: Optional[int] = None,
                  trace_id: Optional[int] = None,
                  tenant_id: int = 0):
        """Ship `slot`'s full resident pages to the host: the
        prefill-engine half of a disaggregated handoff. Returns a
        PageShipment (serve/disagg.py) carrying the chain keys, the
        page rows (+ scale rows on quantized pools) as host numpy, and
        the geometry stamp import_kv validates — or None when the slot
        has no full page yet (the importer simply recomputes). Must
        run while the slot is still mapped (DisaggCluster exports from
        generate's on_finish hook, before the slot is freed)."""
        from .adapters import tenant_prefix_salt
        from .disagg import PageShipment
        self.arch.refuse(handoff=True)
        pages, keys, ntokens = self.cache.export_pages(
            slot, tokens, prev=tenant_prefix_salt(tenant_id))
        if not pages:
            return None
        n = len(pages)
        rows = self._call_counted(
            "export", self._export_jit, self._device_pool(),
            self._h2d(self._pad_idx(pages)))
        # copy the real-page slice: a view would pin the whole
        # pages_per_seq-padded gather buffer for the shipment's life
        host = jax.tree.map(lambda r: np.asarray(r)[:, :n].copy(), rows)
        c = self.cache_cfg
        return PageShipment(
            keys=list(keys), ntokens=int(ntokens),
            k_rows=host.k, v_rows=host.v,
            k_scale_rows=host.k_scale, v_scale_rows=host.v_scale,
            page_size=c.page_size, num_layers=c.num_layers,
            num_heads=c.num_heads, head_dim=c.head_dim,
            kv_dtype=c.kv_dtype, stream_id=stream_id,
            trace_id=trace_id, tenant_id=int(tenant_id))

    def import_kv(self, ship) -> int:
        """Adopt a PageShipment into this engine's pool: the
        decode-engine half of a disaggregated handoff. Registers the
        chain keys (PagedKVCache.import_pages — already-resident keys
        dedupe to nothing) and scatters the needed rows into freshly
        parked pages, so the NEXT generate()'s admission path prefix-
        matches the handed-off prompt exactly as it would a locally
        computed one. Returns the number of pages actually written
        (0 = full dedupe). The caller owns backpressure: check
        `cache.free_pages` first (DisaggCluster skips the import and
        lets the decode engine re-prefill instead of squeezing a
        loaded pool)."""
        c = self.cache_cfg
        if (ship.page_size, ship.num_layers, ship.num_heads,
                ship.head_dim, ship.kv_dtype) != (
                c.page_size, c.num_layers, c.num_heads, c.head_dim,
                c.kv_dtype):
            raise ValueError(
                f"shipment geometry {ship.signature()} does not match "
                f"this pool "
                f"({(c.page_size, c.num_layers, c.num_heads, c.head_dim, c.kv_dtype)})"
            )
        todo = self.cache.import_pages(ship.keys)
        if not todo:
            return 0
        chain = [chain_i for chain_i, _ in todo]
        self._import_pages(
            [page for _, page in todo],
            jax.tree.map(lambda src: src[:, chain],
                         KVPool(ship.k_rows, ship.v_rows,
                                ship.k_scale_rows, ship.v_scale_rows)))
        return len(todo)

    def _import_pages(self, pages: Sequence[int], rows: KVPool) -> None:
        """Scatter host `rows` (a pool of len(pages) pages, numpy
        leaves) into pool pages `pages` through the fixed-shape import
        program: both pad to pages_per_seq, the padding aimed at the
        sink."""
        pad = self.cache_cfg.pages_per_seq - len(pages)

        def padded(r):
            return self._h2d(np.concatenate(
                [r, np.zeros((r.shape[0], pad) + r.shape[2:], r.dtype)],
                axis=1))

        self.pool = self._call_counted(
            "import", self._import_jit, self._device_pool(),
            jax.tree.map(padded, rows), self._h2d(self._pad_idx(pages)))

    def warmup_handoff(self) -> Dict[str, int]:
        """Compile the export/import programs on sink-page dummies (a
        no-op on the pool content), so a DisaggCluster's serving loop
        never compiles after DisaggCluster.warmup(). The import dummies
        are HOST-built arrays, exactly the layout import_kv dispatches
        (a sharded engine would otherwise warm the program against
        device-committed shardings and recompile on the first real,
        host-laid-out shipment). Returns compile_counts()."""
        idx = self._h2d(np.zeros(
            (self.cache_cfg.pages_per_seq,), np.int32))
        rows = self._call_counted(
            "export", self._export_jit, self._device_pool(), idx)
        self._import_pages([], jax.tree.map(
            lambda r: np.zeros((r.shape[0], 0) + r.shape[2:], r.dtype),
            rows))
        return self.compile_counts()

    # ---------------- hierarchical host tier ---------------------------
    def _drain_spills(self) -> int:
        """Ship queued evicted-page content to the host tier through
        the fixed-shape export gather (the disagg program — zero new
        compiles). MUST run before any dispatch that writes the device
        pools: a queued page may already be remapped to a new slot,
        and its old rows survive only until the next jitted write. The
        session calls this right before each mixed dispatch; a reload
        drains before its import scatter for the same reason."""
        store = self.host_tier
        if store is None:
            return 0
        pending = self.cache.take_pending_spills()
        if not pending:
            return 0
        latest = {}          # a page queued twice keeps its newest key
        for page, key in pending:
            latest[page] = key
        todo = [(p, k) for p, k in latest.items()
                if not store.contains(k)]
        if not todo:
            return 0
        c = self.cache_cfg
        shipped = 0
        for i in range(0, len(todo), c.pages_per_seq):
            batch = todo[i:i + c.pages_per_seq]
            rows = self._call_counted(
                "export", self._export_jit, self._device_pool(),
                self._h2d(self._pad_idx([p for p, _ in batch])))
            host = [np.asarray(r) for r in jax.tree.leaves(rows)]
            for j, (_, key) in enumerate(batch):
                if store.put(key, [h[:, j] for h in host]):
                    shipped += 1
        self._host_reload_stats["spilled_pages"] += shipped
        if self.telemetry.enabled and shipped:
            self.telemetry.instant(self._ENGINE_TRACK, "host_spill",
                                   args={"pages": shipped})
        return shipped

    def _host_step_price(self, ctx_len: int) -> float:
        """Predicted seconds of ONE mixed step at this context — the
        recompute side of the spill-vs-recompute decision, from the
        same cost stack the drift calibrator prices; the analytic
        fallback mirrors the router's virtual-clock price."""
        pred = self._drift_predicted(pow2_bucket(max(1, ctx_len)))
        if pred is not None:
            return float(pred[0])
        return 1e-4 * (1.0 + self.mixed_width / 512.0) \
            * (1.0 + ctx_len / 2048.0)

    def _host_reload(self, req, keys, cached_pages,
                     max_pages: int) -> int:
        """The scheduler's admission hook when the host tier is armed:
        extend an HBM prefix match with host-resident pages IF the
        priced DMA beats recomputing those tokens through the prefill
        roofline (TPUMachineModel.host_transfer vs the cost model's
        step price — the paper's priced-placement loop applied to the
        memory hierarchy). Reloaded pages park exactly like a disagg
        import (hashed, refcount 0), so the scheduler's re-match picks
        them up; `free_pages` is unchanged (free -> parked), so the
        admission watermark math the caller already did stays valid.
        Returns the pages made resident; the decision — either way —
        is recorded on the request for explain_request."""
        store, cache = self.host_tier, self.cache
        resident = len(cached_pages)
        run = cache.match_prefix_host(keys, resident)
        if run <= 0:
            return 0
        c = self.cache_cfg
        m = min(run, int(max_pages))
        decision = {"host_matched_pages": int(run),
                    "reloaded_pages": 0, "dma_s": 0.0,
                    "recompute_s": 0.0, "chose": "none"}
        req.host_reload = decision
        if m <= 0:
            return 0
        if self._host_mm is None:
            from ..search.machine_model import default_machine_model
            self._host_mm = default_machine_model(mesh=self.tp_mesh)
        dma_s = float(self._host_mm.host_transfer(
            float(m) * float(c.page_bytes)))
        steps = -(-(m * c.page_size) // max(1, self.prefill_budget))
        recompute_s = steps * self._host_step_price(len(req.prompt))
        decision.update(dma_s=dma_s, recompute_s=recompute_s)
        if dma_s >= recompute_s:
            decision["chose"] = "recompute"
            self._host_reload_stats["recompute_chosen"] += 1
            return 0
        # protect the HBM-matched refcount-0 run from the import's
        # eviction cascade (allocation evicts LRU-oldest)
        cache.touch(cached_pages)
        t0 = time.perf_counter()
        # fetch rows FIRST: on the SHARED store another replica's puts
        # may have evicted part of the matched run since the probe
        fetched = []
        for key in keys[resident:resident + m]:
            rows = store.get(key)
            if rows is None:
                break
            fetched.append(rows)
        # one page of each of the pool's leaves, as export yields it
        leaves, treedef = jax.tree.flatten(self._device_pool())
        one_page = [(a.shape[:1] + a.shape[2:], a.dtype) for a in leaves]
        if not fetched or [(r.shape, r.dtype)
                           for r in fetched[0]] != one_page:
            decision["chose"] = "store_miss"  # raced away / foreign
            return 0                          # geometry: never scatter
        todo = cache.import_pages(keys[resident:resident + len(fetched)])
        if not todo:
            decision["chose"] = "store_miss"
            return 0
        # the allocation above may have queued evictions of its own —
        # their content must ship before the scatter overwrites it
        self._drain_spills()
        self._import_pages(
            [page for _, page in todo],
            treedef.unflatten(
                np.stack([fetched[chain_i][leaf] for chain_i, _ in todo],
                         axis=1) for leaf in range(len(leaves))))
        n = len(todo)
        decision.update(chose="reload", reloaded_pages=n)
        self._host_reload_stats["reload_events"] += 1
        self._host_reload_stats["reload_pages"] += n
        self._host_reload_stats["reload_priced_s"] += dma_s
        self._host_reload_s += dma_s
        if self.telemetry.enabled:
            self.telemetry.span(
                self._ENGINE_TRACK, "host_reload", t0,
                time.perf_counter(),
                args={"trace": req.trace_id, "rid": req.rid,
                      "pages": n, "dma_s": dma_s})
        return n

    # ---------------- bucketing / compile bookkeeping ------------------
    def bucket_for(self, prompt_len: int) -> int:
        for b in self.buckets:
            if prompt_len <= b:
                return b
        raise ValueError(
            f"prompt of {prompt_len} tokens exceeds the largest bucket "
            f"{self.buckets[-1]}")

    def compile_counts(self) -> Dict[str, int]:
        """Compiled-program count per serving function. After warmup()
        these must never grow — the zero-recompile serving contract
        (the whole hot path is the single `mixed` program). Counted by the ProgramRegistry (core/programs.py),
        which owns every serving dispatch: a count increments exactly
        when the registry AOT-compiles a new argument signature, so
        compiles inside warmup_handoff / adapter load can no longer
        hide from it (the old monitoring-snapshot counter missed
        them). Executables restored
        from --program-cache-dir count ZERO — a warm boot reports no
        compiles, which is the point."""
        return self.programs.compile_counts()

    def _h2d(self, x):
        """A host-built step input, placed where this engine runs: on a
        placed replica straight onto its chip (not staged through chip
        0); otherwise uncommitted on the default device — jnp.asarray."""
        return jax.device_put(x, self._home)

    def _device_pool(self) -> KVPool:
        """The resident pool, allocated on first use: head-sharded
        over the serve mesh; single-device, on the placed replica's
        chip (None unplaced)."""
        if self.pool is None:
            sharding = self._home
            if self.tp_mesh is not None:
                from jax.sharding import NamedSharding
                sharding = jax.tree.map(
                    lambda s: NamedSharding(self.tp_mesh, s),
                    KVPool.specs(TENSOR))
            alloc = KVPool.alloc if self.cache_cfg.hybrid is None \
                else HybridPool.alloc
            args = {"pages": self.cache_cfg.num_pages}
            with self.setup_phase("alloc_pool", args):
                self.pool = alloc(self.cache_cfg, sharding)
                args["pool_bytes"] = _tree_bytes(self.pool)
        return self.pool

    # ---------------- adapter pool: device half ------------------------
    def _adapter_slab_shapes(self):
        """{slab: (num_slots,) + per-slot shape} of the device pool —
        the stacked form of adapters._weight_shapes at the pool's
        padded rank/ff, plus the (S,) f32 per-slot scale."""
        from .adapters import _weight_shapes
        ac = self.adapter_cfg
        shapes = {k: (ac.num_slots,) + s for k, s in _weight_shapes(
            ac, ac.rank, ac.ff_dim).items()}
        shapes["scale"] = (ac.num_slots,)
        return shapes

    def _device_adapters(self):
        """The resident slab pytree (lazy, like _device_pool): A/B
        factors at the activation dtype, per-slot scales f32, all
        zeros until tenants load — so slot 0 stays the zero base slab
        forever (nothing ever writes it)."""
        if self.adapters is None:
            return None
        if self._adapter_slabs is None:
            slabs = {}
            for key, shape in self._adapter_slab_shapes().items():
                dt = jnp.float32 if key == "scale" else self.act_dtype
                slabs[key] = jnp.zeros(
                    shape, dt,
                    device=self._home if self._adapter_shardings is None
                    else self._adapter_shardings[key])
            self._adapter_slabs = slabs
        return self._adapter_slabs

    def _adapter_load_impl(self, slabs, slot, rows):
        """Scatter ONE tenant's (A, B, scale) rows into its slot —
        slabs donated in place, rows host-built replicated arrays."""
        return jax.tree.map(
            lambda s, r: s.at[slot].set(r.astype(s.dtype)), slabs,
            rows)

    def register_adapter(self, tenant_id: int, weights, *,
                         scale: float = 1.0) -> None:
        """Register a tenant's LoRA weights with the pool (host copy;
        the device load happens on demand at admission). `weights` is
        the adapters.ADAPTER_SLABS dict at the MODEL's ff width and
        any rank <= the pool rank (zero-padded — exact)."""
        if self.adapters is None:
            raise RuntimeError(
                "engine has no adapter pool (set adapter_rank > 0)")
        self.adapters.register(tenant_id, weights, scale=scale,
                               ff_dim=self.ff_dim)

    def adapter_resident(self, tenant_id: int) -> bool:
        """Whether a tenant's adapter already holds a slab slot — the
        router's adapter-affinity signal (routing to a resident
        replica skips the load stall)."""
        return self.adapters is not None \
            and self.adapters.resident(tenant_id)

    def _drain_adapter_loads(self) -> int:
        """Run every pending tenant load through the jitted scatter —
        the session calls this BEFORE each mixed dispatch, so a lane
        never gathers a slab its tenant has not landed in. Returns
        the number of loads dispatched (a planning-visible stall,
        never a recompile)."""
        if self.adapters is None:
            return 0
        pending = self.adapters.take_pending()
        for slot, tenant in pending:
            w, sc = self.adapters.host_weights(tenant)
            rows = {k: self._h2d(v) for k, v in w.items()}
            rows["scale"] = self._h2d(np.float32(sc))
            self._adapter_slabs = self._call_counted(
                "adapter", self._adapter_load_jit,
                self._device_adapters(), self._h2d(np.int32(slot)), rows)
            if self.telemetry.enabled:
                self.telemetry.instant(
                    self._ENGINE_TRACK, "adapter_load",
                    args={"tenant": tenant, "slot": slot})
        return len(pending)

    def _dispatch_mixed(self, *args, lane_adapters=None):
        """One mixed-step dispatch: `args` are the step's seven lane
        arrays, the (head_rows,) lanes its head runs over and the
        lanes' token source. Returns
        (greedy, topv, topi: a row for each of those lanes, in their
        order; expert counts: the step's (layers, experts) live slots
        per expert on a model with an expert layer, else None; what
        the selection's calls walked, (2,), on a model with a SPARSE
        layer, else None), all still on the device, and
        keeps the returned pool as `self.pool`, so a mid-run audit
        (check_kv_scales from an `on_step` callback, when sequences are
        actually resident) reads THIS step's content. The token source
        ((mixed_width,) int32, -1 = the host's token) names, a lane,
        the row of the PREVIOUS dispatch's `greedy` that holds its
        token (_mixed_impl): that array goes back in as it came out,
        not donated, and this call's is kept for the next. On an adapter-armed engine the lanes' slot
        indices + the slabs ride along (read-only — the slabs are NOT
        donated); unarmed engines pass None (an empty pytree, zero
        trace cost, numerics untouched)."""
        slabs = None
        if self.adapters is not None:
            slabs = self._device_adapters()
            if lane_adapters is None:
                lane_adapters = self._h2d(
                    np.zeros((self.mixed_width,), np.int32))
        if self._greedy is None:
            # nothing dispatched yet: zeros placed as a step's own
            # `greedy` comes out, so that the program is traced once
            rows = np.zeros((self.head_rows,), np.int32)
            if self.tp_mesh is None:
                self._greedy = self._h2d(rows)
            else:
                from jax.sharding import NamedSharding, PartitionSpec
                self._greedy = jax.device_put(
                    rows, NamedSharding(self.tp_mesh, PartitionSpec()))
        *out, self.pool = self._call_counted(
            "mixed", self._mixed_jit, self._step_params,
            self._device_pool(), *args, self._greedy, lane_adapters,
            slabs)
        greedy, topv, topi, *counts = out
        self._greedy = greedy
        # the device's own counts, in the body's order: an expert
        # layer's slots, a selection's walk
        return (greedy, topv, topi,
                counts.pop(0) if self.arch.experts else None,
                counts.pop(0) if self.geometry.select_call_lanes else None)

    def warmup(self) -> Dict[str, int]:
        """Ready the engine's programs once, on throwaway inputs
        (all writes aim at the sink page): compile on a cold boot, or
        dispatch executables the registry restored from
        --program-cache-dir on a warm one (zero compiles). Returns
        compile_counts(); `boot_stats` records which boot this was and
        what it cost (the `replica_boot` span payload), and a cold
        engine with a cache dir armed writes its snapshot back so the
        NEXT boot over this config is warm."""
        t0 = time.perf_counter()
        with self.setup_phase("warmup"):
            self._warm_programs()
        rec = self.programs.boot_record()
        rec["boot_s"] = time.perf_counter() - t0
        rec["warm"] = rec["compiles"] == 0 and rec["restored"] > 0
        rec["attn_impl"] = self.attn_impl
        rec["scan_impl"] = self.scan_impl
        rec["expert_impl"] = self.expert_impl
        rec.update(self._delta_impl)
        # ... and how its slab holds a state (not in the fingerprint:
        # the pool's shapes are)
        rec.update(self.geometry.delta_state)
        # where the start went: the model's phases, then this engine's
        rec["phases"] = list(self.model.boot_stats["phases"]) \
            + list(self._boot_phases)
        rec["setup_s"] = roots_s(rec["phases"])
        self.boot_stats = rec
        if self.programs.cache_dir and self.programs._dirty:
            # read-through write-back: the first (cold) engine over
            # this fingerprint populates the snapshot, every later
            # replica — in-process scale-up or a fresh process —
            # deserializes instead of compiling
            self.programs.save()
        return self.compile_counts()

    def _warm_programs(self) -> None:
        """warmup()'s work: the pool (`alloc_pool`), the throwaway
        mixed step to its end on the device (`first_dispatch`, the
        step's `compile:mixed` inside it), then the adapter's and the
        hand-off's programs."""
        self._device_pool()
        c = self.cache_cfg
        t = self.mixed_width
        z = self._h2d(np.zeros((t,), np.int32))
        pts = self._h2d(
            np.zeros((c.max_seqs, c.pages_per_seq), np.int32))
        with self.setup_phase("first_dispatch"):
            jax.block_until_ready(self._dispatch_mixed(
                z, z, z, z, pts, z, self._h2d(np.ones((t,), np.int32)),
                self._h2d(np.zeros((self.head_rows,), np.int32)),
                self._h2d(np.full((t,), -1, np.int32))))
        if self.adapters is not None:
            # compile the adapter-load scatter on an all-zero row
            # set aimed at the base slot (zeros into zeros — a
            # no-op on content), host-built f32 exactly like a
            # real load (the registered host weights are f32) so
            # the first tenant miss reuses this program
            rows = {k: self._h2d(np.zeros(s[1:], np.float32))
                    for k, s in self._adapter_slab_shapes().items()}
            self._adapter_slabs = self._call_counted(
                "adapter", self._adapter_load_jit,
                self._device_adapters(), self._h2d(np.int32(0)), rows)
        if self.host_tier is not None:
            # spill/reload traffic runs the handoff programs —
            # warm them here or the first eviction under load
            # would compile after the pool snapshots warm counts
            self.warmup_handoff()

    # ---------------- sampling -----------------------------------------
    @staticmethod
    def _sample_params(temperature, top_k, seed, n, cap):
        """Normalize scalar-or-per-request sampling args into one
        Optional[SampleParams] per request."""
        def seq(x):
            if x is None or np.isscalar(x):
                return [x] * n
            if len(x) != n:
                raise ValueError(
                    f"per-request sampling arg has {len(x)} entries "
                    f"for {n} prompts")
            return list(x)
        out = []
        for t, k in zip(seq(temperature), seq(top_k)):
            if t is None or float(t) <= 0.0:
                if t is not None and float(t) < 0.0:
                    raise ValueError(f"temperature must be >= 0, got {t}")
                out.append(None)
                continue
            if k is not None and not (1 <= int(k) <= cap):
                raise ValueError(
                    f"top_k must be in [1, {cap}] (the engine's static "
                    f"top-k head), got {k}")
            out.append(SampleParams(temperature=float(t),
                                    top_k=None if k is None else int(k),
                                    seed=int(seed)))
        return out

    def _pick_token(self, req: Request, greedy: int, topv, topi) -> int:
        """The emitted token for a lane: greedy argmax, or a seeded
        draw from the lane's top-k logits. The RNG is stateless per
        (seed, stream-id, stream-offset + token-index) — stream_id
        defaults to the local rid, so a plain engine keeps the
        historical (seed, rid, index) keying bit-for-bit — which makes
        a fixed seed reproduce a stream exactly, preemption/resume
        replay nothing, and a stream SURVIVE crossing schedulers: the
        disaggregated decode role resumes a handed-off request at
        offset 1, and a routed replica draws the same stream a
        single-replica engine would (docs/serving.md)."""
        sp = req.sample
        if sp is None:
            return int(greedy)
        k = sp.top_k if sp.top_k is not None else self.topk_cap
        v = np.asarray(topv[:k], np.float64) / sp.temperature
        v -= v.max()
        p = np.exp(v)
        p /= p.sum()
        sid = req.rid if req.stream_id is None else req.stream_id
        rng = np.random.default_rng(
            [sp.seed, sid, req.stream_offset + len(req.out_tokens)])
        return int(topi[int(rng.choice(k, p=p))])

    # ---------------- quantized-page verification (tests) -------------
    def check_kv_scales(self) -> None:
        """Device-side scale bookkeeping check for quantized pools
        (the stress tests' companion to PagedKVCache.check_invariants):
        KVPool.check_scales over every row that must hold content — a
        drifted one means e.g. a rollback/preemption interleaving
        that reused a page slot without rewriting its scale. Audits
        RESIDENT (slot, position) rows — which only exist mid-run, so
        the stress tests call this from generate()'s `on_step`
        callback (_dispatch_mixed keeps the live pool each step) —
        plus every prefix-cache-parked page: those are
        complete pages whose content must outlive their writer for a
        later request to attach, and they are what a post-run call
        still covers. No-op on lossless pools."""
        if self.pool is None:
            return
        ps = self.cache_cfg.page_size
        where = [(f"slot {slot} pos {pos}",
                  int(self.cache.page_tables[slot, pos // ps]), pos % ps)
                 for slot in range(self.cache_cfg.max_seqs)
                 for pos in range(int(self.cache.seq_lens[slot]))]
        where += [("cached page", page, off)
                  for page in self.cache.parked_pages()
                  for off in range(ps)]
        self.pool.check_scales(where)

    @staticmethod
    def first_divergence(a, b) -> Optional[int]:
        """Index of the first position where token streams a and b
        differ, or None when one is a prefix of the other (the shared
        scan of assert_token_parity and the bench's prefix-agreement
        metric)."""
        return next((i for i, (x, y) in enumerate(zip(a, b))
                     if x != y), None)

    def assert_token_parity(self, prompts, out, ref, *, margin=None,
                            min_exact_frac=0.0,
                            what="outputs") -> int:
        """The reference-parity gate for generate() outputs (the CI
        bench and the property tests share this one implementation),
        dispatched on the pool format. Lossless pools (kv_exact) gate
        full token identity. Lossy pools (bfloat16/int8 pages) gate
        the relaxed quantized contract instead: each request either
        matches the greedy reference token-for-token, or first
        diverges at a TIE — a position where the reference's own
        top-logit margin over the engine's pick is inside the
        quantization error bound. A real quantization-path bug (a
        mis-indexed scale, a stale page) perturbs logits at O(1) and
        flips comfortable margins, which this catches; an argmax flip
        inside the margin is the priced-in cost of lossy pages (after
        one tie flips, the continuation legitimately diverges, so
        only the first divergence is comparable). Returns the
        fully-identical request count. `margin` defaults to the
        engine's pool-format tie margin (int8 rounds at amax/127, fp8
        at amax/16 — kv_tie_margin)."""
        if margin is None:
            margin = self.kv_tie_margin
        if self.kv_exact:
            for i, (o, r) in enumerate(zip(out, ref)):
                assert list(o) == list(r), (
                    f"{what}: request {i} diverged from reference")
            return len(out)
        exact = 0
        for pr, o, r in zip(prompts, out, ref):
            j = self.first_divergence(o, r)
            if j is None:
                exact += 1
                continue
            ctx = list(pr) + list(r[:j])
            b = self.bucket_for(len(ctx))
            arr = np.zeros((1, b), np.int32)
            arr[0, :len(ctx)] = ctx
            logits = np.asarray(self._forward_jit(
                self.params, jnp.asarray(arr), jnp.int32(len(ctx))))
            gap = float(logits[r[j]] - logits[o[j]])
            assert 0.0 <= gap <= margin, (
                f"{what}: lossy KV pages flipped a non-tie token — "
                f"reference margin {gap:.4f} > {margin} at "
                f"position {j}")
        assert exact >= min_exact_frac * len(prompts), (
            f"{what}: only {exact}/{len(prompts)} requests "
            f"token-identical — quantization error is not bounded at "
            f"tie scale")
        return exact

    # ---------------- robustness --------------------------------------
    def cancel(self, rid: int) -> bool:
        """Host-side cancellation: mark request `rid` of the in-flight
        generate() for abort at the next chunk boundary (its pages and
        prefix-registry pins reclaim through the normal refcount
        machinery). Safe to call from another thread or from an
        `on_step` callback; returns False when no such request is
        active (already finished, or a stale rid)."""
        req = self._active.get(rid)
        if req is None or req.state == RequestState.FINISHED:
            return False
        self._cancels.add(rid)
        return True

    def _sweep_aborts(self, sched) -> None:
        """Chunk-boundary sweep: apply pending cancels and expire
        deadlines. Runs at the top of every serving step, BEFORE the
        scheduler plans — so its slot/pages are free for this very
        step's admissions. A chunk the request holds in a step still in
        flight is dropped when that step lands (ServeSession._land):
        the device runs its programs in order, so a page freed here is
        rewritten only after that step has read and written it."""
        now = time.perf_counter()
        tel = self.telemetry
        live = list(sched.running.values()) + list(sched.waiting)
        expired = 0
        for req in live:
            if req.rid in self._cancels:
                # consume the mark either way: applied, or moot (the
                # request already finished). A long-lived session
                # (ReplicaPool) never reaches generate()'s wholesale
                # clear, and rids restart at 0 in a recovery-reopened
                # session — a stale mark must not cancel a stranger.
                self._cancels.discard(req.rid)
                if sched.abort(req, RequestOutcome.CANCELLED):
                    req.t_finish = now
                    if tel.enabled:
                        tel.instant(self._ENGINE_TRACK, "cancel",
                                    t=now, args={"rid": req.rid,
                                                 "trace": req.trace_id})
            elif req.t_deadline and now >= req.t_deadline:
                if sched.abort(req, RequestOutcome.DEADLINE_EXPIRED):
                    req.t_finish = now
                    expired += 1
                    if tel.enabled:
                        tel.instant(self._ENGINE_TRACK,
                                    "deadline_expired", t=now,
                                    args={"rid": req.rid,
                                          "trace": req.trace_id})
        if expired >= self.DEADLINE_STORM:
            # a deadline STORM (several requests expiring at one chunk
            # boundary) is the latency-collapse signature an operator
            # needs a black box for — one bounded bundle, rate-limited
            self._auto_postmortem("deadline_storm", sched=sched,
                                  detail={"expired_this_sweep": expired})

    def _fail_inflight(self, sched, reqs: Sequence[Request]) -> None:
        """Crash containment (replacing the PR-3-era hard brick): a
        mid-batch exception fails ONLY the in-flight requests — every
        live slot releases through the refcount machinery, the prefix
        registry is dropped (the device arrays its content lived in
        are stale, or consumed by the dispatch that died), and the
        page pools are reallocated lazily if donation ate them. The
        exception still propagates to the caller, but the NEXT
        generate() serves normally on a pool that check_invariants
        vouches for."""
        now = time.perf_counter()
        failed = 0
        for req in reqs:
            if req.state != RequestState.FINISHED:
                if sched.abort(req, RequestOutcome.FAILED):
                    req.t_finish = now
                    failed += 1
        # black-box the crash BEFORE resetting pool state: the bundle
        # must capture the scheduler/pool as the failure left them
        self._auto_postmortem("fault_abort", sched=sched,
                              detail={"failed_inflight": failed})
        self._reset_pool_state()

    def _reset_pool_state(self) -> None:
        """Shared tail of both recovery paths (_fail_inflight and the
        orphaned-slot self-heal): the prefix registry vouches for
        content in device arrays an interrupted batch lost (or donation
        consumed), so drop it wholesale, and reallocate the page pools
        lazily when the interrupted dispatch ate them."""
        self.cache.clear_prefix()   # also drops queued host spills
        self._host_reload_s = 0.0
        if any(a.is_deleted() for a in jax.tree.leaves(self.pool)):
            self.pool = None              # realloc on next use
        self.cache.check_invariants(self.pool)

    # ---------------- telemetry ----------------------------------------
    def _drift_predicted(self, ctx_bucket: int) -> Optional[tuple]:
        """(predicted seconds, per-task-class breakdown) for one mixed
        step at this context bucket, from the SAME cost stack the
        placement search prices (cost_model.serve_step_tasks ->
        simulate_serve_step; the breakdown is the attribution vector
        drift_report folds per task class). The fixed-shape mixed
        program dispatches every lane regardless of occupancy, so the
        prediction varies only with (arch, tp, lane width, context) —
        the cache keys on the context bucket alone and the hot-path
        cost after a bucket's first step is one dict hit. None when
        the cost stack is unavailable."""
        if ctx_bucket not in self._drift_cache:
            try:
                from ..search.simulator import (serve_step_breakdown,
                                                simulate_serve_step)
                arch = self.serve_arch(context=max(1, ctx_bucket))
                # price on the SAME machine model the placement search
                # was calibrated against: --machine-model-file, when
                # set, overrides the default spec (HBM capacity
                # included — a pool whose degree overflows it pays the
                # memory penalty in its virtual step price, exactly
                # what the 2-D mesh search predicted when it rejected
                # that degree)
                mm = None
                mf = getattr(self.config, "machine_model_file", None)
                if mf:
                    from ..search.machine_model import \
                        default_machine_model
                    if getattr(self, "_drift_mm", None) is None:
                        self._drift_mm = default_machine_model(
                            machine_file=mf)
                    mm = self._drift_mm
                self._drift_cache[ctx_bucket] = (
                    float(simulate_serve_step(arch, self.tp, mm,
                                              lanes=self.mixed_width)),
                    serve_step_breakdown(arch, self.tp, mm,
                                         lanes=self.mixed_width))
            except Exception:
                self._drift_cache[ctx_bucket] = None
        return self._drift_cache[ctx_bucket]

    def _drift_regime(self, n_decode: int, pre_bucket: int,
                      ctx_bucket: int) -> str:
        return (f"t={self.tp} kv={self.kv_dtype} dec={n_decode} "
                f"pre={pre_bucket} ctx={ctx_bucket}")

    def set_track_process(self, proc: str) -> None:
        """Re-home this engine's telemetry tracks under a new process
        name (ReplicaPool labels each replica's tracks replica0/1/...
        so a multi-replica trace keeps one track group per replica)."""
        self._proc = str(proc)
        self._ENGINE_TRACK = (self._proc, "engine")
        self._QUEUE_TRACK = (self._proc, "queue")
        self._slot_tracks = []

    def _slot_track(self, slot: int):
        tracks = self._slot_tracks
        while len(tracks) <= slot:
            tracks.append((self._proc, f"slot {len(tracks)}"))
        return tracks[slot]

    def _record_step_telemetry(self, tel, fl: "_Flight",
                               t_start: float, dt: float) -> None:
        """One engine step's telemetry: the step span on the engine
        track, a chunk span per request on the slot track it held at
        the dispatch, queue-wait async spans for this step's
        admissions, preemption instants, pool-occupancy/rung counter
        samples, and the drift sample (measured dt vs the cost model's
        prediction for this step's regime; `t_start` and `dt` as
        ServeSession._land gives them). A `requeue_wait` runs from the
        scheduler's stamp of the eviction (Scheduler._preempt) to
        `t_start` of the step that re-admits, where that step's chunk
        span begins: its dispatch, or the landing before it where it
        was dispatched ahead and the device still ran the step the
        request sat out. Called once the step has LANDED, so a fault that
        kills the step never half-records it. The whole step is built
        as raw event tuples and handed to the bus in ONE
        :meth:`Telemetry.emit` — this runs on every engine step, and
        the per-call overhead of the one-at-a-time recorders is what
        the <= 3% gate budget goes to."""
        plan, step_idx = fl.ev.plan, fl.ev.step_index
        rung, occupancy = fl.rung, fl.util
        t_end = t_start + dt
        dur = max(0.0, dt)
        evs = []
        for req, t_preempt, ordinal in fl.requeued:
            # re-admission after preemption: the span an operator
            # debugging page pressure needs is preempt -> readmit
            # (NOT a duplicate of the original queue wait; ident
            # carries the preemption ordinal so Perfetto pairs
            # each b/e uniquely per eviction)
            ident = f"{req.rid}.{ordinal}"
            evs.append(("b", self._QUEUE_TRACK, "requeue_wait",
                        t_preempt, 0.0, ident,
                        {"rid": req.rid, "trace": req.trace_id,
                         "preemptions": ordinal}))
            evs.append(("e", self._QUEUE_TRACK, "requeue_wait",
                        max(t_preempt, t_start), 0.0, ident, None))
        for req in plan.admitted:
            if not fl.stint[req.rid][0]:
                # first admission: the wait ended where the scheduler
                # stamped it, before this step packed a lane
                evs.append(("b", self._QUEUE_TRACK, "queue_wait",
                            req.t_submit, 0.0, req.rid,
                            {"rid": req.rid, "trace": req.trace_id,
                             "prompt_tokens": len(req.prompt)}))
                evs.append(("e", self._QUEUE_TRACK, "queue_wait",
                            req.t_admit, 0.0, req.rid, None))
        for victim, t_preempt, ordinal in fl.preempted:
            evs.append(("i", self._ENGINE_TRACK, "preempt", t_preempt,
                        0.0, None, {"rid": victim.rid,
                                    "trace": victim.trace_id,
                                    "preemptions": ordinal}))
        drafted = 0
        for ch in plan.chunks:
            name = ("spec_decode" if ch.draft_tokens
                    else "decode" if ch.is_decode else "prefill")
            drafted += len(ch.draft_tokens)
            evs.append(("X",
                        self._slot_track(fl.stint[ch.req.rid][1]), name,
                        t_start, dur,
                        None, {"rid": ch.req.rid,
                               "trace": ch.req.trace_id,
                               "start": ch.start, "end": ch.end,
                               "drafted": len(ch.draft_tokens)}))
        n_dec = plan.num_decode_lanes
        n_pre = plan.num_prefill_lanes
        evs.append(("X", self._ENGINE_TRACK, "step", t_start, dur,
                    None, {"step": step_idx, "decode_lanes": n_dec,
                           "prefill_lanes": n_pre, "drafted": drafted,
                           "rung": rung}))
        evs.append(("C", self._ENGINE_TRACK, "pool_occupancy", t_end,
                    occupancy, None, None))
        evs.append(("C", self._ENGINE_TRACK, "rung", t_end,
                    float(rung), None, None))
        tel.emit(evs)
        if plan.chunks:
            # O(1) context length — Request.context materializes a
            # prompt+out_tokens list copy, far too hot for every step
            ctxs = [len(ch.req.prompt) + len(ch.req.out_tokens)
                    for ch in plan.chunks
                    if ch.is_decode] or [ch.end for ch in plan.chunks]
            ctx_b = pow2_bucket(int(sum(ctxs) / len(ctxs)))
            pre_b = pow2_bucket(n_pre)
            pred = self._drift_predicted(ctx_b)
            if pred is not None:
                tel.record_drift(
                    "serve", self._drift_regime(n_dec, pre_b, ctx_b),
                    pred[0], dt, breakdown=pred[1])

    # ---------------- per-request latency attribution ------------------
    def explain_request(self, rid: int) -> dict:
        """Additive latency attribution for request `rid` of the most
        recent generate()/session run (docs/observability.md
        "Per-request latency attribution"): fold its spans into
        ``{queue, routing, prefill, transfer, decode, preempt_stall,
        retry, other}`` seconds summing to its measured wall latency
        EXACTLY (gated within 1% in CI). Needs telemetry enabled and a
        finished request; rids are ``last_stats['requests'][i]['rid']``.
        Adds ``rid``/``outcome``/``tokens`` to the breakdown."""
        if not self.telemetry.enabled:
            raise RuntimeError(
                "explain_request needs telemetry (pass telemetry= or "
                "set --telemetry/--trace-out)")
        req = self._last_reqs.get(rid)
        if req is None:
            raise KeyError(
                f"rid {rid} is not in the last run "
                f"({sorted(self._last_reqs)})")
        if not req.t_finish:
            raise ValueError(
                f"request {rid} has no finish stamp (outcome "
                f"{req.outcome!r}) — only terminated requests are "
                f"attributable")
        out = self.telemetry.explain_request(
            req.trace_id, req.t_submit, req.t_finish)
        out.update(rid=req.rid, outcome=req.outcome,
                   tokens=len(req.out_tokens),
                   # the admission-time spill-vs-recompute decision
                   # (None when the host tier never matched this
                   # request): priced dma_s vs recompute_s and what
                   # was chosen — next to the host_reload component
                   # the span fold attributes
                   host_reload=getattr(req, "host_reload", None))
        return out

    def fold_attribution(self, registry=None) -> dict:
        """Fold EVERY terminated request of the last run through
        :meth:`explain_request` into `registry` (default: the engine's
        lifetime registry) — the pool-level aggregate
        (`serve_latency_attribution_seconds_total{component}` + the
        derived fraction gauges). Returns the per-component second
        totals of this fold. On-demand, never on the serving hot path
        (the ≤1.03x overhead gate covers recording, not analysis)."""
        from ..utils.telemetry import (REQUEST_COMPONENTS,
                                       fold_attribution)
        m = registry if registry is not None else self.telemetry.metrics
        totals = {c: 0.0 for c in REQUEST_COMPONENTS}
        if not self.telemetry.enabled:
            # no spans to attribute — and the disabled singleton's
            # registry is process-shared, so never write into it
            return totals
        for rid, req in sorted(self._last_reqs.items()):
            if not req.t_finish:
                continue
            b = self.telemetry.explain_request(
                req.trace_id, req.t_submit, req.t_finish)
            fold_attribution(b, m)
            for c, v in b["components"].items():
                totals[c] += v
        return totals

    # ---------------- failure flight recorder ---------------------------
    def postmortem_bundle(self, reason: str = "manual",
                          detail: Optional[dict] = None,
                          sched=None) -> dict:
        """Assemble the bounded post-mortem bundle (docs/observability
        "Failure flight recorder"): the last-N ring spans, metrics +
        drift snapshots, the HBM memory ledger, scheduler and KV-pool
        state, fault accounting and compile counts — everything an
        operator needs to reconstruct a failure post-hoc, bounded so a
        pathological run cannot produce an unbounded artifact. Every
        sub-collector is individually guarded: a broken ledger must
        not cost the spans."""
        tel = self.telemetry
        if sched is None:
            sched = self._session.sched if self._session else None
        bundle = {
            "schema": "flexflow_tpu.postmortem/1",
            "reason": str(reason),
            "detail": dict(detail or {}),
            "created_unix_s": time.time(),
            "engine": {
                "mode": "chunked",
                "mixed_width": self.mixed_width,
                "tensor_parallel": self.tp,
                "kv_dtype": self.kv_dtype,
                "max_seqs": self.cache_cfg.max_seqs,
                "prefill_budget": self.prefill_budget,
                "track_process": self._proc,
            },
            "compile_counts": self.compile_counts(),
            "events": tel.events_tail(self.postmortem_events),
            "events_dropped": tel.dropped_events,
        }
        for key, collect in (
                ("metrics", lambda: tel.metrics.snapshot()),
                ("drift", tel.drift_snapshot),
                ("memory_ledger", self.memory_ledger),
                ("scheduler", (sched.debug_state if sched is not None
                               else lambda: None)),
                ("kv_pool", self.cache.debug_state),
                ("adapter_pool", lambda: (
                    self.adapters.debug_state()
                    if self.adapters is not None else None)),
                ("faults", lambda: {
                    "fired": {s: dict(k) for s, k in
                              getattr(self.faults, "fired",
                                      {}).items()},
                    "site_hits": dict(getattr(self.faults, "_count",
                                              {}))}),
                ("last_stats", lambda: self._trimmed_last_stats())):
            try:
                bundle[key] = collect()
            except Exception as e:   # a collector bug loses ONE section
                bundle[key] = {"error": f"{type(e).__name__}: {e}"}
        return bundle

    def _trimmed_last_stats(self) -> Optional[dict]:
        st = self.last_stats
        if not st:
            return None
        st = dict(st)
        reqs = st.get("requests")
        if isinstance(reqs, list) and len(reqs) > 64:
            st["requests"] = reqs[-64:]
            st["requests_trimmed"] = len(reqs) - 64
        # per-step timing lists grow with the run — the bundle keeps
        # the aggregates, tools/postmortem.py renders from those
        for k in ("decode_step_times_s", "decode_widths",
                  "prefill_times_s"):
            v = st.get(k)
            if isinstance(v, list) and len(v) > 256:
                st[k] = v[-256:]
        return st

    def _postmortem_path(self, reason: str) -> str:
        """THE bundle naming scheme — `postmortem-<reason>-<pid>-<n>
        .json` under postmortem_dir (CWD when unset). One definition:
        the pool/cluster dump_postmortem variants route through their
        lead engine's counter here, and tools/postmortem.py's glob
        patterns depend on it."""
        base = self.postmortem_dir or "."
        os.makedirs(base, exist_ok=True)
        self._postmortem_seq += 1
        return os.path.join(
            base, f"postmortem-{reason}-{os.getpid()}-"
                  f"{self._postmortem_seq}.json")

    def dump_postmortem(self, path: Optional[str] = None,
                        reason: str = "manual",
                        detail: Optional[dict] = None,
                        sched=None) -> str:
        """Write the post-mortem bundle via atomic tmp+rename and
        return the path (default: :meth:`_postmortem_path` under
        ``postmortem_dir``, or the CWD when unset). Explicit trigger —
        always writes, no rate limit. The bundle loads with
        ``tools/postmortem.py``."""
        from ..utils.telemetry import write_json_atomic
        bundle = self.postmortem_bundle(reason, detail, sched=sched)
        if path is None:
            path = self._postmortem_path(reason)
        return write_json_atomic(path, bundle)

    def _auto_postmortem(self, reason: str, sched=None,
                         detail: Optional[dict] = None) -> Optional[str]:
        """Auto-triggered flight-recorder dump (fault-abort, deadline
        storm, rung-4 rejection): only when ``postmortem_dir`` is
        armed, rate-limited, and NEVER raises — a black-box failure
        must not mask the failure it was recording."""
        if not self.postmortem_dir or not self.telemetry.enabled:
            return None
        now = time.monotonic()
        if now - self._postmortem_last < self.POSTMORTEM_MIN_INTERVAL_S:
            return None
        self._postmortem_last = now
        try:
            path = self.dump_postmortem(reason=reason, detail=detail,
                                        sched=sched)
            if self.telemetry.enabled:
                self.telemetry.instant(
                    self._ENGINE_TRACK, "postmortem_dump",
                    args={"reason": reason, "path": path})
            return path
        except Exception:
            return None

    # ---------------- memory ledger ------------------------------------
    def memory_ledger(self) -> dict:
        """Per-device HBM byte accounting for this engine — params, KV
        pages + scale rows, the mixed step's activation estimate, and
        adapter headroom (reserved for the multi-tenant LoRA pool,
        ROADMAP) — next to the simulator's HBM-penalty input
        (cost_model.serve_device_bytes) so a mis-priced memory term is
        visible before it mis-ranks a placement. ``live_bytes`` reads
        the ACTUAL device buffers (shard-aware nbytes); the ledger's
        params + KV accounting must match it (ci.sh gates within 5%).
        Components land as ``serve_hbm_bytes{component=...}`` gauges on
        the engine's registry, so the ledger is scrapeable."""
        from ..search.cost_model import serve_device_bytes
        from ..search.explain import pytree_device_bytes
        c = self.cache_cfg
        t = max(1, self.tp)
        params = pytree_device_bytes(self._step_params)
        kv_pool = float(c.pool_device_bytes)   # values + scale rows
        act_itemsize = float(self.act_dtype.itemsize)
        # live set of ONE mixed step: lane activations through the
        # widest shards (qkv, ffn hidden, logits) — an estimate, the
        # jitted program's true peak is XLA's to schedule
        activations = float(self.mixed_width) * act_itemsize * (
            self.hidden + 3.0 * self.num_heads * self.head_dim / t
            + float(self._ff_pad) / t + float(self._vocab_pad) / t)
        # adapter slab pool (serve/adapters.py): the config-derived
        # per-device bytes; 0.0 unarmed (the pre-adapter headroom line)
        adapter = (float(self.adapter_cfg.pool_device_bytes)
                   if self.adapter_cfg is not None else 0.0)
        total = params + kv_pool + activations + adapter
        pools_live = self.pool is not None
        adapters_live = self._adapter_slabs is not None
        live = params + pytree_device_bytes(
            (self.pool, self._adapter_slabs))
        arch = self.serve_arch()
        sim_input = float(serve_device_bytes(arch, t))
        ledger = {
            "tensor_parallel": t,
            "params_bytes": params,
            "kv_pool_bytes": kv_pool,
            "activation_est_bytes": activations,
            "adapter_bytes": adapter,
            "total_bytes": total,
            # ground truth: live device buffers (params + allocated
            # pools); pools allocate lazily on the first generate()
            "live_bytes": live,
            "pools_live": pools_live,
            "adapters_live": adapters_live,
            "ledger_vs_live": (
                (params + kv_pool
                 + (adapter if adapters_live else 0.0)) / live
                if pools_live and live > 0 else None),
            # the simulator's HBM-penalty input for this engine's arch
            # (steady-state context KV, not the allocated pool)
            "sim_hbm_input_bytes": sim_input,
        }
        try:
            from ..search.machine_model import default_machine_model
            mm = default_machine_model(machine_file=getattr(
                self.config, "machine_model_file", None))
            ledger["hbm_capacity_bytes"] = float(mm.spec.hbm_capacity)
            ledger["hbm_utilization"] = total / ledger[
                "hbm_capacity_bytes"]
        except Exception:
            pass  # no machine model — the byte accounting stands alone
        tel = self.telemetry
        if tel.enabled:
            for comp in ("params", "kv_pool", "activation_est",
                         "adapter", "total", "live",
                         "sim_hbm_input"):
                tel.metrics.set("serve_hbm_bytes",
                                ledger[f"{comp}_bytes"], component=comp)
        return ledger

    def close(self) -> None:
        """Shut down host-side services (the /metrics endpoint thread).
        Idempotent; the engine remains usable for generate() after
        close — only the scrape endpoint goes away."""
        server, self.metrics_server = self.metrics_server, None
        if server is not None:
            server.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ---------------- the serving loop ---------------------------------
    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens, eos_token: Optional[int] = None,
                 temperature=None, top_k=None, sample_seed: int = 0,
                 deadline_s=None, on_step=None, on_finish=None,
                 stream_ids: Optional[Sequence[int]] = None,
                 stream_offset: int = 0,
                 trace_ids: Optional[Sequence[int]] = None,
                 tenant_ids: Optional[Sequence[int]] = None
                 ) -> List[List[int]]:
        """Decode a ragged batch under continuous batching.
        `max_new_tokens` is an int or a per-prompt sequence; greedy by
        default, per-request seeded temperature/top-k sampling when
        `temperature` is given (scalar or per-prompt; 0 = greedy).
        Returns the generated tokens (prompt excluded) per prompt, in
        order. Per-request latency, prefix-cache/preemption/utilization
        counters, and per-token timings land in `self.last_stats`
        (render with utils/profiling.serve_report).

        Robustness: `deadline_s` (scalar or per-prompt; falls back to
        FFConfig.serve_request_deadline; 0/None = none) bounds each
        request's wall time from submission — expiry aborts it at a
        chunk boundary with outcome "deadline_expired" and its partial
        tokens are returned. `cancel(rid)` (rids are
        `last_stats["requests"][i]["rid"]`, assigned in prompt order)
        aborts a request the same way. `on_step(step_index)` is called
        after every engine step — the hook chaos tests drive cancels
        and invariant checks from. `on_finish(req)` is called when a
        request completes, BEFORE its slot releases — its pages are
        still mapped, which is the window a disaggregated prefill
        engine exports them in (serve/disagg.py passes
        `lambda r: export_kv(r.slot, r.context)` here). A mid-batch
        exception fails only the in-flight requests and the engine
        keeps serving (_fail_inflight).

        `stream_ids` (per-prompt, default None = the local rid) and
        `stream_offset` key the seeded sampling draws to an engine-
        independent stream identity (docs/serving.md "Sampled
        streams"): a DisaggCluster resumes each request's stream at
        offset 1 on the decode role, and a routed replica draws the
        exact stream a single-replica engine would — token streams
        survive crossing schedulers instead of being refused.

        It runs through a :class:`ServeSession` (the steppable form
        the multi-replica router drives directly): generate() is
        submit-everything + drain over it, so both tiers serve through
        one code path."""
        if isinstance(max_new_tokens, int):
            max_new_tokens = [max_new_tokens] * len(prompts)
        if len(max_new_tokens) != len(prompts):
            raise ValueError(
                f"max_new_tokens has {len(max_new_tokens)} entries for "
                f"{len(prompts)} prompts")
        samples = self._sample_params(temperature, top_k, sample_seed,
                                      len(prompts), self.topk_cap)
        if deadline_s is None and self.default_deadline > 0:
            deadline_s = self.default_deadline
        if deadline_s is not None and np.isscalar(deadline_s):
            deadline_s = [deadline_s] * len(prompts)
        if deadline_s is not None and len(deadline_s) != len(prompts):
            raise ValueError(
                f"deadline_s has {len(deadline_s)} entries for "
                f"{len(prompts)} prompts")
        if stream_ids is not None and len(stream_ids) != len(prompts):
            raise ValueError(
                f"stream_ids has {len(stream_ids)} entries for "
                f"{len(prompts)} prompts")
        if trace_ids is not None and len(trace_ids) != len(prompts):
            raise ValueError(
                f"trace_ids has {len(trace_ids)} entries for "
                f"{len(prompts)} prompts")
        if tenant_ids is not None and len(tenant_ids) != len(prompts):
            raise ValueError(
                f"tenant_ids has {len(tenant_ids)} entries for "
                f"{len(prompts)} prompts")
        if tenant_ids is not None and any(tenant_ids) \
                and self.adapters is None:
            raise ValueError(
                "tenant_ids != 0 need an armed adapter pool "
                "(adapter_rank > 0); this engine serves base-only")
        return self._generate_session(
            prompts, max_new_tokens, samples, eos_token, deadline_s,
            stream_ids, stream_offset, on_step, on_finish, trace_ids,
            tenant_ids)

    def _build_stats(self, reqs, sched, *, wall, steps, retries0,
                     decode_times, decode_widths, prefill_times,
                     util) -> dict:
        """The last_stats dict (ServeSession.stats_dict(): generate()
        and every routed replica)."""
        c = self.cache_cfg
        cache = self.cache
        total_new = sum(len(r.out_tokens) for r in reqs)
        peak_util = float(np.max(util)) if util else 0.0
        return {
            "requests": [
                {"rid": r.rid, "trace_id": r.trace_id,
                 "tenant": int(getattr(r, "tenant_id", 0)),
                 "prompt_tokens": len(r.prompt),
                 "new_tokens": len(r.out_tokens),
                 "preemptions": r.preemptions,
                 "outcome": r.outcome,
                 "ttft_s": (r.t_first_token - r.t_submit
                            if r.t_first_token else None),
                 "latency_s": (r.t_finish - r.t_submit
                               if r.t_finish else None)}
                for r in reqs],
            "mode": "chunked",
            # the paged-attention implementation that ran and where
            "attn_impl": self.attn_impl,
            "scan_impl": self.scan_impl,
            "expert_impl": self.expert_impl,
            **self._delta_impl,
            # the paged calls a step makes, and those that read their
            # pool's leaf where it lies
            **mixers.paged_calls(self.geometry),
            "devices": [int(d.id) for d in self.devices],
            "wall_s": wall,
            "total_new_tokens": total_new,
            "tokens_per_sec": total_new / wall if wall > 0 else 0.0,
            "steps": steps,
            "decode_steps": len(decode_times),
            "decode_step_times_s": decode_times,
            "decode_widths": decode_widths,
            "prefill_times_s": prefill_times,
            "compile_counts": self.compile_counts(),
            # prefix cache / chunked prefill / preemption instrumentation
            "prompt_tokens_total": sched.stats["prompt_tokens"],
            "prefill_tokens_computed": sched.stats["prefill_lane_tokens"],
            "prefix_hit_tokens": sched.stats["prefix_hit_tokens"],
            "preemptions": sched.stats["preemptions"],
            # speculative decoding instrumentation: decode_tokens are
            # the tokens decode chunks emitted, decode lane-steps the
            # times a sequence occupied a decode lane — their ratio is
            # per-sequence steps per token, exactly 1.0 without
            # speculation and < 1.0 when accepted drafts advance a
            # sequence several tokens per dispatched step
            "spec_tokens": self.spec_tokens,
            "spec_drafted_tokens": sched.stats["spec_drafted_tokens"],
            "spec_accepted_tokens": sched.stats["spec_accepted_tokens"],
            "spec_acceptance": (
                sched.stats["spec_accepted_tokens"]
                / sched.stats["spec_drafted_tokens"]
                if sched.stats["spec_drafted_tokens"] else 0.0),
            "decode_tokens": int(sum(decode_widths)),
            "steps_per_decode_token": (
                sched.stats["decode_lane_tokens"] / sum(decode_widths)
                if decode_widths else 0.0),
            "page_util_mean": float(np.mean(util)) if util else 0.0,
            "page_util_max": peak_util,
            # robustness instrumentation (docs/robustness.md): abort /
            # deadline / rejection outcomes, retried dispatches, and
            # how far up the degradation ladder this batch climbed
            "cancelled": sched.stats["cancelled"],
            "deadline_expired": sched.stats["deadline_expired"],
            "rejected": sched.stats["rejected"],
            "rejected_requests": [(rr.rid, rr.reason)
                                  for rr in sched.rejected_requests],
            "retries": self._retries - retries0,
            "degradation_rung_max": sched.stats["degradation_rung_max"],
            "rung_steps": list(sched.stats["rung_steps"]),
            "spec_shed_steps": sched.stats["spec_shed_steps"],
            "cache": dict(cache.stats),   # engine-lifetime counters
            # tensor-parallel sharding block (None single-device):
            # mesh shape, heads/device, per-device pool bytes, and the
            # analytic per-step collective payload (serve_report
            # renders it; tools/serve_bench.py --workload shard records
            # it next to the measured A/B)
            "sharding": self._sharding_stats(),
            # KV pool: storage format, itemsize-derived byte accounting,
            # effective capacity vs f32 pages, and the ragged kernel
            # v2 work-item accounting (serve_report renders both)
            "kv_pool": {
                **cache.pool_report(),
                # pool_report's occupancy is instantaneous and every
                # slot is already released here — report the run's
                # peak residency (what --kv-pool-mb tuning needs)
                "occupancy": peak_util,
                "kv_exact": self.kv_exact,
                "attn_block_kv": self.attn_block_kv,
                "attn_dispatch_passes": {
                    k: v * steps for k, v in ragged_dispatch_passes(
                        self.mixed_width, c.pages_per_seq,
                        self.attn_block_pages, Q_ROWS,
                        slot_changes=c.max_seqs
                    ).items()},
            },
            # hierarchical host tier (None unarmed): the shared
            # store's occupancy + spill/reload/hit counters plus THIS
            # engine's reload accounting (a ReplicaPool's replicas
            # report one store, each with its own engine counters)
            "host_tier": (
                {**self.host_tier.report(),
                 **{k: (float(v) if isinstance(v, float) else int(v))
                    for k, v in self._host_reload_stats.items()}}
                if self.host_tier is not None else None),
            # multi-tenant adapter pool (None unarmed): slot geometry,
            # residency, and the hit/evict/load/stall counters the
            # tenant-labeled metrics fold reads (serve/adapters.py)
            "adapter_pool": (
                {**self.adapters.pool_report(),
                 **{k: int(v) for k, v in self.adapters.stats.items()},
                 "blocked_steps":
                     sched.stats["adapter_blocked_steps"]}
                if self.adapters is not None else None),
        }

    def start_session(self) -> "ServeSession":
        """Open an incremental serving session — the engine hook the
        multi-replica router tier drives (serve/router.py): submit
        requests at any time, advance ONE mixed step per
        :meth:`ServeSession.step` call, ``close()`` when done.
        generate() is submit-everything + drain over the same session
        machinery, so a routed replica serves through exactly the code
        path the single-engine contracts (token parity, zero
        recompiles, invariants) are proven on. At most one live session per engine (the session's scheduler
        owns the slots)."""
        return ServeSession(self)

    def _generate_session(self, prompts, max_new_tokens, samples,
                          eos_token, deadline_s, stream_ids,
                          stream_offset, on_step, on_finish,
                          trace_ids=None,
                          tenant_ids=None) -> List[List[int]]:
        """generate()'s loop: one ServeSession, every prompt submitted
        up front, stepped to drain."""
        session = self.start_session()
        reqs = session.reqs
        tel = self.telemetry
        try:
            # submits inside the containment: a submit-time rejection
            # (e.g. an unregistered adapter tenant) must fail the
            # batch AND close the session, not orphan it open
            for i, (prompt, mnt, sp) in enumerate(
                    zip(prompts, max_new_tokens, samples)):
                session.submit(
                    prompt, mnt, eos_token=eos_token, sample=sp,
                    deadline_s=(deadline_s[i] if deadline_s is not None
                                else None),
                    stream_id=(stream_ids[i] if stream_ids is not None
                               else None),
                    stream_offset=stream_offset, on_finish=on_finish,
                    trace_id=(trace_ids[i] if trace_ids is not None
                              else None),
                    tenant_id=(int(tenant_ids[i])
                               if tenant_ids is not None else 0))
            while True:
                ev = session.step()
                if ev is None:
                    break
                if ev.dispatched and on_step is not None:
                    on_step(ev.step_index)
        except Exception:
            self._fail_inflight(session.sched, reqs)
            raise
        finally:
            session.close()
            self._active.clear()
            self._cancels.clear()
            # chaos runs stay inspectable post-hoc (docs/robustness.md):
            # the injector's fired accounting and the Chrome trace
            # flush even when a fault aborts the run, and an unwritable
            # --trace-out path must not fail a generate that already
            # produced tokens
            if tel.enabled:
                tel.record_faults(self.faults)
                if self.trace_out:
                    try:
                        tel.export_chrome_trace(self.trace_out)
                    except OSError:
                        pass
        self.cache.check_invariants(self.pool)
        assert self.cache.free_pages == self.cache_cfg.usable_pages, \
            "pages leaked"
        self.last_stats = session.stats_dict()
        # fold this run into the engine-lifetime telemetry registry —
        # the same canonical definitions serve_report renders from
        if tel.enabled:
            serve_metrics(self.last_stats, registry=tel.metrics)
        return [list(r.out_tokens) for r in reqs]

    def generate_reference(self, prompts: Sequence[Sequence[int]],
                           max_new_tokens,
                           eos_token: Optional[int] = None
                           ) -> List[List[int]]:
        """Naive no-cache greedy decode: re-forward the WHOLE sequence
        for every new token, one request at a time. O(n^2) per token —
        the correctness oracle generate() is tested against."""
        if isinstance(max_new_tokens, int):
            max_new_tokens = [max_new_tokens] * len(prompts)
        if len(max_new_tokens) != len(prompts):
            raise ValueError(
                f"max_new_tokens has {len(max_new_tokens)} entries for "
                f"{len(prompts)} prompts")
        out: List[List[int]] = []
        for prompt, mnt in zip(prompts, max_new_tokens):
            if mnt < 1:  # mirror scheduler.submit's contract
                raise ValueError(f"max_new_tokens must be >= 1, got {mnt}")
            toks = list(prompt)
            new: List[int] = []
            while len(new) < mnt:
                b = self.bucket_for(len(toks))
                arr = np.zeros((1, b), np.int32)
                arr[0, :len(toks)] = toks
                logits = self._forward_jit(self.params, jnp.asarray(arr),
                                           jnp.int32(len(toks)))
                tok = int(jnp.argmax(logits))
                new.append(tok)
                toks.append(tok)
                if eos_token is not None and tok == eos_token:
                    break
            out.append(new)
        return out


class StepEvents:
    """What ONE engine step did, handed out by the
    :meth:`ServeSession.step` call in which it LANDED (its results
    reached the host), which is the call that dispatched it or the one
    after — the router tier's
    window into a replica's progress (serve/router.py advances each
    replica's virtual clock by a cost-model-priced step and stamps
    TTFT/TPOT off these). Every field is of that one step: its plan,
    its counters, its tokens. ``ahead`` is True for a step that was
    dispatched before the step before it was fetched. ``emitted`` is
    [(request, tokens emitted
    this step)] (speculation can emit several per step), ``finished``
    the requests that completed THIS step, ``ctx_mean`` the mean
    decode-context length (the drift calibrator's pricing regime),
    ``kv_bytes_read`` the K/V page bytes the step's attention kernel
    calls fetch, ``attn_items`` / ``attn_rows`` the work items ONE of
    those calls runs for the live lanes and the query rows they hold
    (rows / items: how often lanes share an item); over ALL of the
    step's paged calls (mixers.attn_calls: each walks the full pages'
    list or the window layers'), ``grid_steps`` is the grid steps the
    device walks: the lists' own lengths (a call's grid ends at its
    list's `count`; the inactive tiles keep an item each on the sink
    page, so it is more than the live lanes' items),
    ``live_steps`` those that hold a live lane's work item,
    ``short_steps`` those of them that take the kernel's one-lane body
    (0 on a model whose calls do not hold it: has_short_body) and
    ``live_rows`` the query rows in them (an item has room for
    Q_ROWS); ``lanes`` is the rows the head and the sampler run over
    (the engine's fixed ``head_rows``: the emitting lanes' rows,
    gathered before the head, padded with lane 0's) and ``emitters``
    the chunks that emit, a lane each whose logits the host reads;
    ``paged_calls`` is the paged calls the step makes and
    ``paged_calls_in_place`` those that read their pool's leaf where
    it lies (mixers.paged_calls: all on a head-packed pool, none on an
    unpacked one, whose layer slab XLA copies out for every call);
    ``topv`` / ``topi`` the step's fetched (lanes, k) top-k logits and
    their token ids and ``emit_lanes`` the ROW of those arrays at which
    each entry of ``emitted`` starts (an entry's tokens come from that
    row and the ones after it: what a check against a reference reads,
    the engine's logits through the cache; the name is older than the
    gather, when a row was a lane); on a model with
    an expert layer ``expert_counts`` is the step's (layers, experts)
    live slots per expert as the device counted them,
    ``expert_slots`` the slots the live lanes asked for (live lanes x
    experts a token x layers), ``expert_dropped`` the slots no expert
    counted (0: the layer is dropless), ``experts_touched`` the
    (layer, expert) pairs with at least one slot, ``expert_bytes``
    their weights' bytes (what the expert phase reads) and
    ``expert_load_max`` the fullest expert's slots; where this chip
    holds a share of the experts (arch.experts_held) the counts are
    over the held experts, ``slots_held`` their sum beside
    ``expert_slots`` (what the live lanes routed, held here or not),
    and ``shared_bytes`` what the step reads of the shared experts; on
    a model with window layers ``lanes_past_window`` is the live lanes
    whose length exceeds the window; on a model whose
    slots hold state besides pages (kv_cache.HybridSpec)
    ``state_bytes`` is the scan states and tails the step reads and
    writes for its runs, ``ssm_runs`` the runs (segments) each scan
    covers, ``window_kv_bytes`` / ``full_kv_bytes`` the page fetches of
    the window layers' calls and of the calls on the full layer's
    pages (its own and every cross layer's); on a model that selects
    its context (arch.selector_dim; SELECT_COUNTS) ``sparse_lanes`` is
    the live lanes past the selector's dense_len, ``blocks_visible`` /
    ``blocks_selected`` the blocks those lanes see and select over all
    sparse layers and key/value heads; ``selector_bytes`` is what the
    DEVICE gathers a step of compressed keys: every stretch of lanes
    fetches one copy, its main sequence's, and the stray lanes, those
    of another sequence, a copy each, a stretch of them a trip
    (``score_tiles`` the stretches a layer, ``score_shared_tiles``
    those with no stray lane: sparse_paged.main_slots); what only the
    device can count, fetched with the step's tokens and so set when a
    step LANDS (mixers.SELECT_LANDED_COUNTS): ``select_items`` the grid
    steps the selection's paged calls walk over all sparse layers and
    key/value heads, ``select_block_fetches`` the selection blocks
    those items fetch, ``selected_kv_bytes`` their K and V — what the
    device moves of the selected context;
    ``kv_bytes_read`` stays the paged calls' page fetches;
    ``dispatched``
    False for a call in which no step landed: a planning-only
    iteration (rung-4
    rejections / whole-set preemption under injected pressure — the
    scheduler's forced-progress rule guarantees re-planning
    converges; ``plan`` is that plan), or a call that dispatched a
    step and left it in flight (``plan`` None: the next call brings
    its events)."""

    __slots__ = ("dispatched", "ahead", "step_index", "plan", "emitted",
                 "finished", "ctx_mean", "wall_s", "host_reload_s",
                 "topv", "topi", "emit_lanes", *mixers.STEP_COUNTS,
                 "expert_counts", "expert_slots", "expert_dropped",
                 "experts_touched", "expert_bytes", "expert_load_max",
                 "slots_held", "shared_bytes")

    def __init__(self, plan=None):
        self.dispatched = False
        self.ahead = False
        self.step_index = -1
        self.plan = plan
        self.emitted: List[Tuple[Request, int]] = []
        self.finished: List[Request] = []
        self.ctx_mean = 0
        self.wall_s = 0.0
        # priced host-tier DMA seconds this step's admissions spent
        # (the router adds it to the virtual clock; wall mode measures
        # it inside the step wall time naturally)
        self.host_reload_s = 0.0
        # what the step's mixers do for its lanes (mixers.step_counts)
        for key in mixers.STEP_COUNTS:
            setattr(self, key, 0)
        self.topv = self.topi = None
        self.emit_lanes: List[int] = []
        self.expert_counts = None
        self.expert_slots = 0
        self.expert_dropped = 0
        self.experts_touched = 0
        self.expert_bytes = 0
        self.expert_load_max = 0
        self.slots_held = 0
        self.shared_bytes = 0


class _Flight:
    """A step that was dispatched and has not landed: its events so
    far, its outputs still on the device, and what the landing needs of
    the plan as it was packed (ServeSession._step / _land)."""

    __slots__ = ("ev", "outputs", "lane", "emitters", "spec_emitters",
                 "t_dispatch", "rung", "util", "lands_first", "rows",
                 "stint", "preempted", "requeued")

    def __init__(self, ev, outputs, lane, emitters, spec_emitters,
                 t_dispatch, rung, util, lands_first):
        self.ev = ev
        self.outputs = outputs  # (greedy, topv, topi, counts, selected)
        self.lane = lane            # live lanes
        self.emitters = emitters
        self.spec_emitters = spec_emitters
        self.t_dispatch = t_dispatch
        self.rung = rung
        self.util = util            # the pool's occupancy as planned
        self.lands_first = lands_first
        # request -> the row of `greedy` that holds its token: the next
        # plan's token source
        self.rows = {ch.req.rid: row for ch, row in emitters}
        # request -> its preemptions and its slot at the dispatch: a
        # request that is running at the landing, in the same stint,
        # still holds its chunk
        plan = ev.plan
        self.stint = {ch.req.rid: (ch.req.preemptions, ch.req.slot)
                      for ch in plan.chunks}
        # the evictions this plan made and the waits its re-admissions
        # end, (request, the scheduler's stamp, the preemption's
        # ordinal), read at the dispatch: a later plan may evict the
        # request again before this step lands
        self.preempted = [(v, v._t_requeue, v.preemptions)
                          for v in plan.preempted]
        self.requeued = [(r, r._t_requeue, r.preemptions)
                         for r in plan.admitted
                         if r._t_requeue is not None]
        for req, _, _ in self.requeued:
            req._t_requeue = None

    def holds(self, ch: ChunkPlan) -> bool:
        req = ch.req
        return req.state == RequestState.RUNNING \
            and req.preemptions == self.stint[req.rid][0]


class ServeSession:
    """Incremental (steppable) serving over one ServeEngine.

    The engine hook of the multi-replica tier (serve/router.py): a
    ReplicaPool keeps ONE long-lived session per replica, submits
    requests as routed traffic arrives, and advances each replica one
    mixed step at a time — while generate() drives the very same
    session submit-all + drain, so the two tiers cannot fork. The
    session owns the scheduler (and with it the engine's slots); at
    most one is live per engine until ``close()``.

    One step: sweep cancels/deadlines at the chunk boundary, plan,
    pack lanes, dispatch the ONE mixed program; then, once its results
    are fetched (it LANDS), bookkeeping first / emission second /
    speculative verification last. At most one step is in flight AHEAD
    of the host: step N+1 is planned, packed, uploaded and dispatched
    while step N runs, and N is fetched and emitted after that
    dispatch, so the device does not wait for the host. The one token
    of N that N+1 needs is read on the device (_pack's token source).
    What the next plan needs is booked at dispatch (residency, that
    the request has a token more), what needs the token's value at
    landing (out_tokens, finishing, the prefix cache's keys). A step
    whose results the next plan cannot do without lands before that
    plan is made (_lands_first), in today's order."""

    def __init__(self, engine: ServeEngine):
        if engine._session is not None:
            raise RuntimeError(
                "engine already has a live ServeSession — close() it "
                "first (the session's scheduler owns the slots)")
        self.eng = engine
        cache = engine.cache
        c = engine.cache_cfg
        if cache.free_slots != c.max_seqs:
            # orphan recovery: a previous batch died without
            # _fail_inflight running — reclaim slots/pages, reset the
            # pool state, serve on
            cache.release_all()
            engine._reset_pool_state()
        self.sched = ContinuousBatchingScheduler(
            cache, prefill_token_budget=engine.prefill_budget,
            admit_watermark=engine.admit_watermark,
            spec_tokens=engine.spec_tokens, drafter=engine.drafter,
            faults=engine.faults,
            degrade_ladder=engine.degrade_ladder,
            reject_stalls=engine.reject_stalls,
            adapter_pool=engine.adapters,
            host_reload=(engine._host_reload
                         if engine.host_tier is not None else None))
        self.reqs: List[Request] = []
        self._on_finish: Dict[int, object] = {}
        self.decode_times: List[float] = []
        self.decode_widths: List[int] = []
        self.prefill_times: List[Tuple[int, float]] = []
        self.util: List[float] = []
        # steps whose packed lanes returned a non-finite top-k logit
        # (a NaN anywhere upstream of the head reaches them)
        self.nonfinite_steps = 0
        # the paged calls' live grid steps so far, and those of them
        # that took the kernel's one-lane body (stats_dict)
        self.attn_steps = {"live": 0, "short": 0}
        # running totals of the expert layer's counters (expert_stats)
        self.expert_totals = {"steps": 0, "slots": 0, "dropped": 0,
                              "touched": 0, "bytes": 0}
        self.expert_counts_total = None     # (layers, experts) int64
        self._retries0 = engine._retries
        self._rejected_seen = 0   # flight-recorder rejection trigger
        # the step dispatched and not landed yet, if any (_Flight), how
        # many were dispatched, how many of them ahead of the landing
        # of the one before, and the emitting rows of a step in flight
        # that nobody read (their request had left by the landing)
        self._flight: Optional[_Flight] = None
        self.steps_dispatched = 0
        self.steps_ahead = 0
        self.lanes_dropped = 0
        self._t_landed = 0.0
        self._t0 = time.perf_counter()
        engine._device_pool()
        engine._session = self

    # ---------------- submission ---------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int, *,
               eos_token: Optional[int] = None,
               sample: Optional[SampleParams] = None,
               deadline_s: Optional[float] = None,
               stream_id: Optional[int] = None,
               stream_offset: int = 0, on_finish=None,
               trace_id: Optional[int] = None,
               tenant_id: int = 0) -> Request:
        """Queue one request (admission happens at the next step()).
        `sample` is a ready SampleParams (None = greedy); `stream_id`/
        `stream_offset` key its sampling stream (engine._pick_token);
        `trace_id` carries an upstream tier's trace context (router /
        disagg — None mints a fresh one); `on_finish(req)` fires when
        THIS request completes, before its slot releases; `tenant_id`
        selects the tenant's registered LoRA adapter (0 = the base
        model — the only tenant an unarmed engine serves)."""
        r = self.sched.submit(prompt, int(max_new_tokens),
                              eos_token=eos_token, sample=sample,
                              stream_id=stream_id,
                              stream_offset=stream_offset,
                              trace_id=trace_id,
                              tenant_id=tenant_id)
        r.t_submit = time.perf_counter()
        if deadline_s is None and self.eng.default_deadline > 0:
            deadline_s = self.eng.default_deadline
        if deadline_s and float(deadline_s) > 0:
            r.t_deadline = r.t_submit + float(deadline_s)
        if on_finish is not None:
            self._on_finish[r.rid] = on_finish
        self.reqs.append(r)
        self.eng._active[r.rid] = r
        return r

    def has_work(self) -> bool:
        """Whether a step() call has anything to do: a request waits or
        runs, or a step is in flight."""
        return self._flight is not None or self.sched.has_work()

    # ---------------- emission -----------------------------------------
    def _finish(self, ev: StepEvents, req: Request) -> None:
        req.t_finish = time.perf_counter()
        cb = self._on_finish.pop(req.rid, None)
        if cb is not None:
            cb(req)
        self.sched.finish(req)
        self.eng._active.pop(req.rid, None)
        ev.finished.append(req)

    def _emit(self, ev: StepEvents, chunk: ChunkPlan, greedy, topv,
              topi) -> None:
        req = chunk.req
        tok = self.eng._pick_token(req, greedy, topv, topi)
        req.out_tokens.append(tok)
        ev.emitted.append((req, 1))
        if len(req.out_tokens) == 1:
            req.t_first_token = time.perf_counter()
        if req.is_done():
            self._finish(ev, req)

    def _emit_spec(self, ev: StepEvents, chunk: ChunkPlan, row0: int,
                   greedy, topv, topi) -> int:
        """Verify a speculative decode chunk and emit its step's
        tokens: walk rows row0..row0+k of the step's outputs (the
        context token's lane and the k drafts', which _pack put side by
        side), picking each lane's token exactly as sequential
        decode would — lane j's logits are valid BECAUSE every earlier
        pick matched the draft that fed lane j+1 — and stop at the
        first mismatch (that pick IS the corrected token), at EOS /
        max_new, or after the bonus token when every draft held. Then
        the scheduler commits the verified prefix and rolls the
        rejected tail's pages back. Returns the number of tokens
        emitted (1 when k=0 — the plain decode step, bit for bit)."""
        eng = self.eng
        req = chunk.req
        k = len(chunk.draft_tokens)
        matched = emitted = 0
        for j in range(k + 1):
            row = row0 + j
            tok = eng._pick_token(req, greedy[row], topv[row], topi[row])
            # (no t_first_token stamp: only decode chunks speculate,
            # and a decoding request already emitted)
            req.out_tokens.append(tok)
            emitted += 1
            ok = j < k and tok == chunk.draft_tokens[j]
            if ok:
                matched += 1
            if req.is_done() or not ok:
                break
        self.sched.complete_spec_chunk(chunk, matched)
        if eng.telemetry.enabled:
            eng.telemetry.instant(
                eng._slot_track(req.slot), "spec_verify",
                args={"rid": req.rid, "trace": req.trace_id,
                      "drafted": k, "accepted": matched,
                      "emitted": emitted})
        ev.emitted.append((req, emitted))
        ev.emit_lanes.append(row0)
        if req.is_done():
            self._finish(ev, req)
        return emitted

    # ---------------- the step -----------------------------------------
    def _pack(self, plan):
        """The plan's chunks as the mixed program's host-built lane
        arrays (mixed_width wide; inactive lanes aim at the sink page
        with a visible length of 1) and, last of them, the (head_rows,)
        lanes the step's head runs over: the emitters' lanes first, then
        each speculative chunk's 1 + k lanes side by side, padded with
        lane 0; after it the (mixed_width,) token source: -1 for a lane
        whose token the host has, else the row of the in-flight step's
        `greedy` that will hold it — the one token of a context not
        landed yet, where the request emitted in that step.
        -> (arrays in dispatch order, lane_adapters or None,
        live lanes, emitters, spec_emitters: (chunk, the ROW of the
        step's outputs that holds its last lane's logits), the paged
        kernel's work for these lanes: `work_items` of one call plus
        `kv_bytes_read`, what all layers' calls fetch, and LIVE_COUNTS,
        the step's fixed shape against its live work over all of its
        calls: mixers.step_counts)."""
        eng = self.eng
        cache = eng.cache
        t_w = eng.mixed_width
        ps = eng.cache_cfg.page_size
        tokens = np.zeros((t_w,), np.int32)
        positions = np.zeros((t_w,), np.int32)
        write_pages = np.zeros((t_w,), np.int32)   # sink by default
        write_offs = np.zeros((t_w,), np.int32)
        lane_slots = np.zeros((t_w,), np.int32)
        lane_lens = np.ones((t_w,), np.int32)      # NaN-free padding
        token_src = np.full((t_w,), -1, np.int32)
        flight_rows = self._flight.rows if self._flight is not None else {}
        # inactive lanes gather adapter slot 0 (the zero base slab)
        lane_adapters = np.zeros((t_w,), np.int32) \
            if eng.adapters is not None else None
        lane = 0
        emit_at: List[Tuple[ChunkPlan, int]] = []      # (chunk, lane)
        spec_at: List[Tuple[ChunkPlan, int]] = []
        for ch in plan.chunks:
            ctx = ch.req.context
            row = cache.page_tables[ch.req.slot]
            aslot = int(getattr(ch.req, "adapter_slot", 0) or 0)
            for pos in range(ch.start, ch.end):
                if pos < len(ctx):
                    tokens[lane] = ctx[pos]
                else:
                    token_src[lane] = flight_rows[ch.req.rid]
                positions[lane] = pos
                write_pages[lane] = row[pos // ps]
                write_offs[lane] = pos % ps
                lane_slots[lane] = ch.req.slot
                lane_lens[lane] = pos + 1
                if lane_adapters is not None:
                    lane_adapters[lane] = aslot
                lane += 1
            if ch.draft_tokens:
                spec_at.append((ch, lane - 1))
                for j, d in enumerate(ch.draft_tokens):
                    pos = ch.end + j
                    tokens[lane] = d
                    positions[lane] = pos
                    write_pages[lane] = row[pos // ps]
                    write_offs[lane] = pos % ps
                    lane_slots[lane] = ch.req.slot
                    lane_lens[lane] = pos + 1
                    if lane_adapters is not None:
                        lane_adapters[lane] = aslot
                    lane += 1
            elif ch.emits:
                emit_at.append((ch, lane - 1))
        assert lane <= t_w, (
            f"scheduler packed {lane} lanes into a {t_w}-lane step")
        # the head's rows: a row an emitter, 1 + k a speculative chunk,
        # lane 0's for the rest
        read = [ln for _, ln in emit_at]
        emitters = [(ch, row) for row, (ch, _) in enumerate(emit_at)]
        spec_emitters: List[Tuple[ChunkPlan, int]] = []
        for ch, ln in spec_at:
            spec_emitters.append((ch, len(read)))
            read += range(ln, ln + 1 + len(ch.draft_tokens))
        rows = eng.head_rows
        assert len(read) <= rows, (
            f"the plan's emitters need {len(read)} rows of the step's "
            f"head, which has {rows}")
        head_lanes = np.zeros((rows,), np.int32)
        head_lanes[:len(read)] = read
        arrays = (tokens, positions, write_pages, write_offs,
                  cache.page_tables, lane_slots, lane_lens, head_lanes,
                  token_src)
        # what the mixers will do for these lanes: the count is made
        # where the lanes are made, and a plan that passes a grid's
        # bound raises there (serve/mixers.py)
        work = mixers.step_counts(
            eng.geometry, cache.page_tables, positions, lane_slots,
            lane_lens, live_lanes=lane, runs=len(plan.chunks),
            head_rows=rows, emitters=len(emitters) + len(spec_emitters))
        return arrays, lane_adapters, lane, emitters, spec_emitters, work

    def _count_experts(self, ev: StepEvents, counts: np.ndarray,
                       live: int) -> None:
        """The step's expert counters from its (layers, experts) live
        slots per expert, and the session's running totals."""
        arch = self.eng.arch
        absent = 0
        if arch.experts_held is not None:
            # a share's counts: the held experts', then the live slots
            # whose expert is absent
            absent, counts = int(counts[:, -1].sum()), counts[:, :-1]
            ev.slots_held = int(counts.sum())
            ev.shared_bytes = arch.shared_bytes
        ev.expert_counts = counts
        ev.expert_slots = live * arch.experts_per_token * counts.shape[0]
        ev.expert_dropped = ev.expert_slots - int(counts.sum()) - absent
        assert ev.expert_dropped == 0, (
            f"{ev.expert_dropped} of {ev.expert_slots} expert slots of "
            f"the step's {live} live lanes reached no expert")
        ev.experts_touched = int((counts > 0).sum())
        ev.expert_bytes = ev.experts_touched * arch.expert_bytes
        ev.expert_load_max = int(counts.max())
        tot = self.expert_totals
        tot["steps"] += 1
        tot["slots"] += ev.expert_slots
        tot["dropped"] += ev.expert_dropped
        tot["touched"] += ev.experts_touched
        tot["bytes"] += ev.expert_bytes
        if self.expert_counts_total is None:
            self.expert_counts_total = np.zeros(counts.shape, np.int64)
        self.expert_counts_total += counts

    def expert_stats(self) -> Optional[dict]:
        """Running totals of the expert layer over this session's
        steps (None without one): slots asked for and dropped, (layer,
        expert) pairs touched and their weight bytes, and `counts`, the
        (layers, experts) slots each expert has taken."""
        if not self.eng.arch.experts:
            return None
        counts = self.expert_counts_total
        return {**self.expert_totals,
                "counts": None if counts is None else counts.copy()}

    def step(self) -> Optional[StepEvents]:
        """Advance the session by one call: plan and dispatch the next
        engine step, and land (fetch, book, emit) the one before it —
        or, where that one's results are needed for the plan
        (_lands_first), land it first and return. Returns the
        StepEvents of the step that LANDED in this call, whole and of
        one step; an empty StepEvents (no plan, `dispatched` False)
        from a call that dispatched a step and landed none, which is
        what the first call of a stream returns; a plan's own where
        nothing could be dispatched; None when nothing waits, runs or
        is in flight (the session is drained: no request survived the
        abort sweep). The whole call is one phase span,
        `serve_step`, and each part of it a child span
        (Telemetry.timed: docs/observability.md "Phase spans"): `fetch`
        and `emit` are the landing step's, the others the dispatched
        step's."""
        eng = self.eng
        try:
            with eng.telemetry.timed(eng._ENGINE_TRACK, "serve_step"):
                return self._step()
        except Exception:
            # whatever is in flight died with this call, or is the
            # caller's to fail (_fail_inflight): nothing is left to land
            self._flight = None
            raise

    def _lands_first(self, emitters, spec_emitters) -> bool:
        """Whether the step just dispatched must land before the next
        plan is made, read from the plan alone. (a) The plan needs its
        tokens' VALUES: a speculative chunk or a request that could
        draft (the drafter reads the context), a request that samples
        (_pick_token draws on the host); the host tier and the adapter
        pool move device state between steps from the host, and keep
        their step-by-step order too. (b) It changes who is running and
        the host can foresee it: an emitter reaches max_new_tokens in
        it, and the caller — a closed loop's client, a router — acts on
        the finish before the next plan. Every other step runs while
        the next is planned."""
        eng = self.eng
        if spec_emitters or eng.host_tier is not None \
                or eng.adapters is not None:
            return True
        for ch, _ in emitters:
            req = ch.req
            if req.sample is not None or req.spec is not None \
                    or len(req.out_tokens) + req.inflight \
                    >= req.max_new_tokens:
                return True
        return False

    def _step(self) -> Optional[StepEvents]:
        eng = self.eng
        sched = self.sched
        cache = eng.cache
        c = eng.cache_cfg
        timed, track = eng.telemetry.timed, eng._ENGINE_TRACK
        prev = self._flight
        if prev is not None and prev.lands_first:
            # the tail of the step before, as it always was: the caller
            # sees its events before anything new is swept or planned
            self._flight = None
            return self._land(prev)
        # chunk boundary: cancels and expired deadlines leave the
        # system HERE, before any of this step's chunks exist
        with timed(track, "sweep"):
            eng._sweep_aborts(sched)
        if not sched.has_work():
            self._flight = None
            return None if prev is None else self._land(prev)
        with timed(track, "schedule"):
            plan = sched.schedule()
        ev = StepEvents(plan)
        # claim the priced host-tier DMA this plan's admissions spent
        # (carried even on planning-only iterations)
        ev.host_reload_s, eng._host_reload_s = eng._host_reload_s, 0.0
        if sched.stats["rejected"] > self._rejected_seen:
            # rung-4 structured rejection: the ladder refused service —
            # exactly the state an operator wants black-boxed (one
            # bundle per rate-limit window, not one per rejection)
            self._rejected_seen = sched.stats["rejected"]
            eng._auto_postmortem("rejection", sched=sched)
        if not plan.chunks:
            # every waiting request was rejected (rung 4) or the
            # running set was preempted whole under injected pressure;
            # the next step() re-plans (forced progress guarantees
            # this cannot spin). A step in flight lands meanwhile
            if prev is None:
                return ev
            self._flight = None
            landed = self._land(prev)
            landed.host_reload_s += ev.host_reload_s
            return landed
        with timed(track, "pack"):
            (arrays, lane_adapters, lane, emitters, spec_emitters,
             work) = self._pack(plan)
            counted = eng.geometry.counted
            for key in mixers.EVENT_COUNTS + counted:
                setattr(ev, key, work[key])
            self.attn_steps["live"] += ev.live_steps
            self.attn_steps["short"] += ev.short_steps
        with timed(track, "drain"):
            # land any adapters this plan admitted BEFORE their lanes
            # dispatch — the planning-visible load stall, not a
            # recompile
            eng._drain_adapter_loads()
            # ship queued evictions to the host tier BEFORE the
            # dispatch overwrites their pages (the spill-safety window)
            eng._drain_spills()
        ev.step_index = self.steps_dispatched
        ev.ahead = prev is not None
        tp = time.perf_counter()
        with timed(track, "upload"):
            dev = [eng._h2d(a) for a in arrays]
            dev_adapters = None if lane_adapters is None \
                else eng._h2d(lane_adapters)
        with timed(track, "dispatch", {
                "step": ev.step_index, "live": lane,
                "prefill": plan.num_prefill_lanes,
                "decode": plan.num_decode_lanes,
                "kv_bytes": ev.kv_bytes_read,
                "items": ev.attn_items, "rows": ev.attn_rows,
                **{key: work[key] for key in counted},
                "ahead": int(ev.ahead), "dispatched": 1}):
            outputs = eng._dispatch_mixed(*dev,
                                          lane_adapters=dev_adapters)
        self.steps_dispatched += 1
        self.steps_ahead += ev.ahead
        # what the next plan needs, booked now: the chunks' tokens are
        # resident for every later program, and each emitter has a
        # token more (its value lands with the step)
        for ch in plan.chunks:
            if not ch.draft_tokens:
                sched.chunk_dispatched(ch)
        for ch, _ in emitters:
            ch.req.inflight += 1
        cur = self._flight = _Flight(
            ev, outputs, lane, emitters, spec_emitters, tp, sched.rung,
            1.0 - cache.free_pages / c.usable_pages,
            self._lands_first(emitters, spec_emitters))
        if prev is not None:
            return self._land(prev)
        if cur.lands_first:
            self._flight = None
            return self._land(cur)
        return StepEvents()

    def _land(self, fl: "_Flight") -> StepEvents:
        """Fetch a dispatched step's results and do what needs their
        values: commit the pages its chunks completed, emit its tokens,
        finish what they finish. A chunk whose request has left the
        running set since the dispatch (an EOS the host could not
        foresee, a cancel, a deadline, a preemption) is neither
        committed nor emitted: its row is dropped (`lanes_dropped`).
        Its pages could be freed at once, since every later program is
        ordered behind this one on the device. -> the step's events.
        `wall_s`, `decode_times`, `prefill_times` and the telemetry's
        step span are the time from the later of this step's dispatch
        and the landing before it to this landing: the step's own time
        where it ran ahead, dispatch to fetch as before where not."""
        eng = self.eng
        sched = self.sched
        timed, track = eng.telemetry.timed, eng._ENGINE_TRACK
        ev, plan = fl.ev, fl.ev.plan
        greedy, topv, topi, counts, selected = fl.outputs
        with timed(track, "fetch"):
            greedy = np.asarray(greedy)
            topv = np.asarray(topv)
            topi = np.asarray(topi)
            ev.topv, ev.topi = topv, topi
            if counts is not None:
                self._count_experts(ev, np.asarray(counts), fl.lane)
            landed = {}
            if selected is not None:
                landed = mixers.select_landed(eng.geometry,
                                              np.asarray(selected))
                for key, n in landed.items():
                    setattr(ev, key, n)
        now = time.perf_counter()
        t_start = max(fl.t_dispatch, self._t_landed)
        dt = now - t_start
        self._t_landed = now
        # what the landed step counted on the device: an expert
        # layer's slots, a selection's walk (a model may hold both)
        counted = dict(landed)
        if eng.arch.experts:
            counted.update(expert_slots=ev.expert_slots,
                           experts_touched=ev.experts_touched,
                           expert_bytes=ev.expert_bytes)
            if eng.arch.experts_held is not None:
                counted["shared_bytes"] = ev.shared_bytes
        with timed(track, "emit", {"step": ev.step_index, **counted}
                   if counted else None):
            # every fetched row is a live lane's (the padding is lane
            # 0's)
            if not np.isfinite(topv).all():
                self.nonfinite_steps += 1
            self.util.append(fl.util)
            if eng.telemetry.enabled:
                eng._record_step_telemetry(eng.telemetry, fl, t_start,
                                           dt)
            # bookkeeping FIRST (page commits hash the context as it
            # was when the chunk ran), emission second; speculative
            # chunks verify LAST — their residency bookkeeping is a
            # function of the tokens they emit
            for ch in plan.chunks:
                if not ch.draft_tokens and fl.holds(ch):
                    sched.chunk_landed(ch)
            dec_tokens = 0
            for ch, row in fl.emitters:
                if not fl.holds(ch):
                    self.lanes_dropped += 1
                    continue
                ch.req.inflight -= 1
                self._emit(ev, ch, greedy[row], topv[row], topi[row])
                ev.emit_lanes.append(row)
                if ch.is_decode:
                    dec_tokens += 1
            for ch, row in fl.spec_emitters:
                dec_tokens += self._emit_spec(ev, ch, row, greedy, topv,
                                              topi)
        if plan.num_decode_lanes:
            self.decode_times.append(dt)
            # width = tokens this step's decode chunks EMITTED
            # (speculation makes it exceed the decode-lane count),
            # the denominator of per-token decode latency
            self.decode_widths.append(dec_tokens)
        if plan.num_prefill_lanes:
            self.prefill_times.append((plan.num_prefill_lanes, dt))
        ev.dispatched = True
        ev.wall_s = dt
        ctxs = [len(ch.req.prompt) + len(ch.req.out_tokens)
                for ch in plan.chunks if ch.is_decode] \
            or [ch.end for ch in plan.chunks]
        ev.ctx_mean = int(sum(ctxs) / len(ctxs))
        return ev

    # ---------------- stats / lifecycle --------------------------------
    def stats_dict(self) -> dict:
        """This session's last_stats-shaped dict so far (generate()
        publishes it as engine.last_stats; a ReplicaPool folds it per
        replica via serve_metrics(..., replica=...))."""
        stats = self.eng._build_stats(
            self.reqs, self.sched,
            wall=time.perf_counter() - self._t0,
            steps=len(self.util), retries0=self._retries0,
            decode_times=self.decode_times,
            decode_widths=self.decode_widths,
            prefill_times=self.prefill_times, util=self.util)
        stats["nonfinite_logit_steps"] = self.nonfinite_steps
        c = self.eng.cache_cfg
        stats["cache_bytes_per_token"] = c.cache_bytes_per_token
        stats["cache_bytes_constant_per_seq"] = c.constant_bytes_per_seq
        stats["attn_steps"] = dict(self.attn_steps)
        # how often a step ran ahead of the landing before it
        # (`steps` counts the landed ones)
        stats["steps_dispatched"] = self.steps_dispatched
        stats["steps_ahead"] = self.steps_ahead
        stats["lanes_dropped"] = self.lanes_dropped
        if self.eng.arch.experts:
            stats["experts"] = self.expert_stats()
        return stats

    def close(self) -> None:
        """Release the session (idempotent): the engine can open a new
        one. A step still in flight lands first (its events go
        unread). Does NOT force-abort live requests — drain first, or
        use engine.cancel / _fail_inflight for abnormal teardown."""
        fl, self._flight = self._flight, None
        if fl is not None:
            self._land(fl)
        if self.eng._session is self:
            self.eng._session = None
        if self.reqs:
            # the closed session's requests become the engine's
            # explain_request(rid) namespace (rids restart per session)
            self.eng._last_reqs = {r.rid: r for r in self.reqs}
        for r in self.reqs:
            self.eng._active.pop(r.rid, None)
            self.eng._cancels.discard(r.rid)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
