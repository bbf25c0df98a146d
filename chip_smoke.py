#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py          # no arguments, one process, no network

Drives the two normal entry points once, at the full width of one model,
on whatever accelerator JAX reports, and checks what comes out by the
repo's own means. It never sets `jax_platforms`, never retries and never
falls back: without a TPU whose `device_kind` the repo holds peaks for
it exits non-zero before building a model and prints no result line.
One line per phase (`PASS`/`FAIL`, wall seconds split into compile and
steady state), then a `details: {...}` line of JSON with every phase's
figures; the exit code is the conjunction; the last line of stdout is
exactly `{"ok": bool, "device": {"platform", "kind", "count"}}`, the
device as JAX reports it, and carries no other key (the driver checks
it). These are observations for CHANGES.md, not benchmark metrics.

Model — the repo's own decoder LM, `models/transformer.
build_transformer_lm` (learned positions, pre-LN, ReLU feed-forward,
full multi-head attention, untied head): the one architecture
ServeEngine reads and a graph the ordinary executor trains. Its block
is the OPT family's, so the widths are OPT-1.3B's.

  assumed (quoted from memory of the public config; the sandbox has no
  network): hidden 2048, 32 heads of 64, feed-forward 8192, vocabulary
  50272, 2048 learned positions, 24 layers; bf16 compute, f32 masters,
  bf16 KV pages.

No width is cut. Depth is cut for TRAINING only, so that f32 masters,
gradients and activations fit one 16 GB chip (plain SGD: no slots):

  params(L) = 2 * 50272*2048 (embedding + untied head) + 2048*2048
              (positions) + L * (4*2048^2 + 2*2048*8192)
            = 210.1M + 50.3M * L          (L = 8: 612M, 2.3 GiB in f32)
  At batch 1, sequence 2048 (the LM graph is built at the position-table
  length) XLA's memory analysis of the train step, compiled ahead of
  time for a v5e, gives: L = 8: 2.3 GiB arguments (masters, donated) +
  5.4 GiB temporaries (gradients, bf16 weight casts, saved activations,
  the 2048 x 50272 logits in bf16 and f32) = 7.7 GiB; L = 12: 10.9 GiB;
  L = 8 at batch 2: 12.8 GiB. Hence L = 8, batch 1 per data shard.
  Serving keeps the model's own 24 layers: 5.7 GiB of f32 masters, 257
  bf16 pages of 16 tokens x 24 layers (0.8 GiB), one 520-lane step's
  activations — 8.7 GiB by the same analysis (2.2 GiB per chip at
  tensor_parallel 4).

Phases (the first thing that fails ends the run):

  device   platform / device_kind / count; tpu and a known kind or exit.
  kernel   `paged_attention_ragged_v2` compiled by Mosaic at the engine's
           own geometry (520 lanes, page 16, 128 pages per sequence)
           against `_ragged_jnp` on seeded pages, bf16 and f32. Logits,
           not tokens: with random weights the arg-max flips on rounding.
           Two lane layouts: every lane of another sequence than its
           neighbour (the longest grid the kernel can be asked for),
           and a mixed step as the engine packs it (decode lanes, one
           chunk, an inactive tail) on the engine's own grid bound.
           Tolerance: the kernel is f32-accurate (f32 operands at
           HIGHEST MXU precision; bf16 operands that are exact, the
           probabilities as two bf16 halves, f32 sums);
           the jnp twin is too once XLA's default one-pass
           bf16 f32-dot is overridden ("highest"), leaving summation
           order: 2e-5. A bf16 output adds half a unit in its 8th bit at
           magnitudes up to ~4: 2e-2. The twin gathers every lane's
           whole table, so it runs on every 11th lane (0.8 GB per
           operand), not on all 520 (8.7 GB).
  train    FFModel.compile(optimizer, loss) with a small search budget on
           a data x model mesh over the chips (1 x 1 on one), so the
           search, the calibrated machine model and the executor all
           run; then train_batch on one fixed seeded batch. Every loss
           finite, the last below the first, no compile after the first
           step; timing closed by the loss fetch.
  serve    ServeEngine at the default lane layout (512 + 8), warmup(),
           two waves of seeded prompts: one longer than the prefill
           budget (chunking), one repeating the first wave's prefix
           (prefix cache), 32 new tokens each. Every request completes
           in range, every step's top-k logits finite, cache invariants
           hold, compile_counts() does not move after warm-up, and the
           attention implementation the engine RESOLVED is the
           Mosaic-compiled Pallas kernel.

On more than one chip the same two phases run again — the trainer on a
2 x n/2 data x model mesh over all chips, the engine at tensor_parallel
= device count — and a ReplicaPool of one-chip replicas (over the
depth-cut model) serves seeded traffic; every parameter's and page
pool's device set is printed.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import json
import os
import sys
import time
import traceback


@dataclasses.dataclass(frozen=True)
class Widths:
    hidden: int
    heads: int
    ffn: int
    vocab: int
    positions: int
    layers: int          # serving depth (the model's own)
    train_layers: int    # training depth (cut: see the docstring)
    train_batch: int     # per data-parallel shard
    page_size: int = 16
    new_tokens: int = 32


OPT_1_3B = Widths(hidden=2048, heads=32, ffn=8192, vocab=50272,
                  positions=2048, layers=24, train_layers=8,
                  train_batch=1)

TRAIN_STEPS = 5
LEARNING_RATE = 0.02
SEARCH_BUDGET = 8


def say(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Seconds the backend spent compiling and persistent-cache hits,
    from jax.monitoring's event stream (a cache hit skips the backend
    compile, so it adds no seconds)."""

    def __init__(self):
        from jax import monitoring
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def device_sets(tree) -> str:
    """The distinct device-id sets the arrays of a pytree live on."""
    import jax
    sets = {tuple(sorted(int(d.id) for d in leaf.devices()))
            for leaf in jax.tree_util.tree_leaves(tree)
            if hasattr(leaf, "devices")}
    return " ".join(str(list(s)) for s in sorted(sets))


# ------------------------------------------------------------------ phases
def kernel_phase(w: Widths, interpret: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.kernels.paged_ragged_v2 import (
        _ragged_jnp, build_work_list, max_work_items,
        paged_attention_ragged_v2)

    cfg = FFConfig()
    h, d, ps = w.heads, w.hidden // w.heads, w.page_size
    lanes = cfg.serve_prefill_budget + cfg.serve_max_seqs
    seqs, pp, pages = cfg.serve_max_seqs, -(-w.positions // ps), cfg.kv_num_pages
    rng = np.random.RandomState(0)
    # ragged residency that fills the pool: one full-length sequence,
    # the rest geometric, never more pages than the pool has
    lens = [w.positions]
    while len(lens) < seqs:
        lens.append(max(1, lens[-1] // 3 + 1))
    while sum(-(-n // ps) for n in lens) > pages - 1:
        lens[lens.index(max(lens))] //= 2
    table = np.zeros((seqs, pp), np.int32)
    free = list(rng.permutation(np.arange(1, pages)))
    for s, n in enumerate(lens):
        for i in range(-(-n // ps)):
            table[s, i] = int(free.pop())
    sub = np.arange(0, lanes, 11)            # the lanes the twin checks
    # scattered: every lane of another sequence than its neighbour —
    # one run a lane, the kernel's own (longest) grid
    slots = (np.arange(lanes) % seqs).astype(np.int32)
    pos = np.array([rng.randint(0, lens[s]) for s in slots], np.int32)
    pos[:seqs] = np.array(lens) - 1          # every sequence's tail too
    # mixed, as ServeSession._pack lays a step out: a decode lane per
    # sequence but the first, that one's last tokens as one chunk, an
    # inactive tail on the sink — on the grid the engine would prove
    chunk = min(lens[0], (lanes - seqs) * 3 // 4)
    m_slots = np.zeros(lanes, np.int32)
    m_pos = np.zeros(lanes, np.int32)
    m_slots[:seqs - 1] = np.arange(1, seqs)
    m_pos[:seqs - 1] = np.array(lens[1:]) - 1
    m_pos[seqs - 1:seqs - 1 + chunk] = lens[0] - chunk + np.arange(chunk)
    bp = 8                                   # pages a block, mixed
    layouts = {"scattered": (slots, pos, None),
               "mixed": (m_slots, m_pos,
                         max_work_items(lanes, pp, bp, slot_changes=seqs))}
    worst = {}
    for dtype, tol in ((jnp.bfloat16, 2e-2), (jnp.float32, 2e-5)):
        q = jnp.asarray(rng.randn(lanes, h, d), dtype)
        kp = jnp.asarray(rng.randn(pages, ps, h, d), dtype)
        vp = jnp.asarray(rng.randn(pages, ps, h, d), dtype)
        args = (kp, vp, jnp.asarray(table))
        for layout, (l_slots, l_pos, bound) in layouts.items():
            def call(q, kp, vp, t, s, n, bound=bound):
                work = None if bound is None else build_work_list(
                    t, s, n, page_size=ps, block_pages=bp, max_items=bound)
                return paged_attention_ragged_v2(
                    q, kp, vp, t, s, n, work=work, use_pallas=True,
                    interpret=interpret)
            out = jax.jit(call)(q, *args, jnp.asarray(l_slots),
                                jnp.asarray(l_pos + 1))
            with jax.default_matmul_precision("highest"):
                ref = jax.jit(lambda q, kp, vp, t, s, n: _ragged_jnp(
                    q, kp, vp, t, s, n, d ** -0.5))(
                    q[sub], *args, jnp.asarray(l_slots[sub]),
                    jnp.asarray(l_pos[sub] + 1))
            got = np.asarray(out, np.float32)
            name = f"{jnp.dtype(dtype).name}/{layout}"
            if got.shape != (lanes, h, d) or not np.isfinite(got).all():
                raise AssertionError(f"{name}: kernel output shape "
                                     f"{got.shape} / non-finite values")
            err = float(np.max(np.abs(got[sub]
                                      - np.asarray(ref, np.float32))))
            worst[name] = err
            if not err <= tol:
                raise AssertionError(
                    f"{name}: kernel vs jnp max abs error "
                    f"{err:.3g} over tolerance {tol:g}")
    return {"impl": "pallas_interpret" if interpret else "pallas",
            "lanes": lanes, "heads": h, "head_dim": d, "page_size": ps,
            "pages_per_seq": pp, "max_abs_err": worst}


def _build_lm(w: Widths, layers: int, *, batch: int, mesh=None, **cfg_kw):
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import build_transformer_lm
    cfg = FFConfig(batch_size=batch, compute_dtype="bfloat16",
                   kv_dtype="bfloat16", kv_page_size=w.page_size, seed=0,
                   **cfg_kw)
    return build_transformer_lm(
        cfg, vocab_size=w.vocab, max_seq_len=w.positions, hidden=w.hidden,
        num_heads=w.heads, num_layers=layers, ff_dim=w.ffn, mesh=mesh)


def train_phase(w: Widths, clock: CompileClock, n: int,
                keep: list = None) -> dict:
    """Train on the first n chips: a 2 x n/2 data x model mesh (1 x 1
    on one chip — still a mesh, so the search runs). The trained model
    is appended to `keep` when the caller wants it (the pool phase)."""
    import numpy as np

    from flexflow_tpu import SGDOptimizer, make_mesh
    from flexflow_tpu.core.losses import sparse_categorical_crossentropy

    gc.collect()         # the previous phase's model leaves the chips
    shape = (2, n // 2) if n > 1 and n % 2 == 0 else (1, n)
    mesh = make_mesh(shape, ("data", "model"))
    batch = w.train_batch * shape[0]
    t0 = time.perf_counter()
    lm = _build_lm(w, w.train_layers, batch=batch, mesh=mesh,
                   search_budget=SEARCH_BUDGET)
    # the graph ends in the head's logits, not a softmax
    lm.compile(optimizer=SGDOptimizer(lr=LEARNING_RATE),
               loss_type=functools.partial(sparse_categorical_crossentropy,
                                           from_logits=True),
               metrics=[])
    setup_s = time.perf_counter() - t0
    rng = np.random.RandomState(0)
    tokens = rng.randint(1, w.vocab, (batch, w.positions)).astype(np.int32)
    data = {"tokens": tokens,
            "positions": np.tile(np.arange(w.positions, dtype=np.int32),
                                 (batch, 1)),
            "label": np.roll(tokens, -1, axis=1)}
    c0 = clock.seconds
    t0 = time.perf_counter()
    losses = [float(lm.train_batch(data)["loss"])]   # compiles the step
    first_s = time.perf_counter() - t0
    warm = dict(lm.compile_counts())
    t0 = time.perf_counter()
    for _ in range(TRAIN_STEPS - 1):
        losses.append(float(lm.train_batch(data)["loss"]))  # fetch = sync
    steady_s = (time.perf_counter() - t0) / (TRAIN_STEPS - 1)
    info = {"mesh": dict(mesh.shape), "layers": w.train_layers,
            "batch": batch, "seq": w.positions, "setup_s": round(setup_s, 2),
            "first_step_s": round(first_s, 2),
            "steady_step_s": round(steady_s, 4),
            "step_compile_s": round(clock.seconds - c0, 2),
            "losses": [round(x, 4) for x in losses],
            "search_engine": (getattr(lm, "search_stats", None)
                              or {}).get("engine", "no search ran"),
            "param_devices": device_sets(lm.state.params)}
    if not np.isfinite(losses).all():
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses}")
    if lm.compile_counts() != warm or sum(warm.values()) < 1:
        raise AssertionError(f"train step recompiled after warm-up: "
                             f"{warm} -> {lm.compile_counts()}")
    if keep is not None:
        keep.append(lm)
    return info


def _prompts(w: Widths, budget: int):
    """Two seeded waves: one prompt longer than the prefill budget
    (chunked prefill), mixed lengths, and in the second wave one that
    repeats a first-wave prompt's leading pages (prefix cache)."""
    import numpy as np
    rng = np.random.RandomState(1)
    room = w.positions - w.new_tokens - 1

    def prompt(n):
        return [int(t) for t in rng.randint(1, w.vocab, size=max(2, n))]

    long_ = prompt(min(room, budget + budget // 6))
    shared = prompt(min(room, 8 * w.page_size + 2))
    wave1 = [long_, shared, prompt(9), prompt(min(room, budget // 2 + 4))]
    wave2 = [shared[:6 * w.page_size] + prompt(w.page_size + 14),
             prompt(min(room, 40))]
    return wave1, wave2


def serve_phase(w: Widths, lm, clock: CompileClock, interpret: bool,
                tensor_parallel: int = 1) -> dict:
    from flexflow_tpu.kernels.paged_ragged_v2 import PALLAS, PALLAS_INTERPRET
    from flexflow_tpu.serve import ServeEngine

    c0 = clock.seconds
    t0 = time.perf_counter()
    eng = ServeEngine(lm, interpret=interpret,
                      tensor_parallel=tensor_parallel
                      if tensor_parallel > 1 else None)
    try:
        warm = dict(eng.warmup())
        warmup_s = time.perf_counter() - t0
        compile_s = clock.seconds - c0
        wave1, wave2 = _prompts(w, eng.prefill_budget)
        t0 = time.perf_counter()
        outs, steps, finished, hits, nonfinite = [], 0, [], 0, 0
        for wave in (wave1, wave2):
            outs += eng.generate(wave, w.new_tokens)
            st = eng.last_stats
            steps += st["steps"]
            finished += [r.get("outcome", "completed")
                         for r in st["requests"]]
            hits += st["prefix_hit_tokens"]
            nonfinite += st["nonfinite_logit_steps"]
        steady_s = time.perf_counter() - t0
        want = PALLAS_INTERPRET if interpret else PALLAS
        info = {"tensor_parallel": eng.tp, "layers": eng.num_layers,
                "lanes": eng.mixed_width, "attn_impl": eng.attn_impl,
                "warmup_s": round(warmup_s, 2),
                "warmup_compile_s": round(compile_s, 2),
                "generate_s": round(steady_s, 2), "steps": steps,
                "step_s": round(steady_s / max(1, steps), 4),
                "requests": len(outs),
                "new_tokens": sum(len(o) for o in outs),
                "prefix_hit_tokens": hits,
                "compile_counts": eng.compile_counts(),
                "param_devices": device_sets(eng._step_params),
                "pool_devices": device_sets(eng.pool)}
        if eng.attn_impl != want or st["attn_impl"] != want:
            raise AssertionError(f"attention ran as {eng.attn_impl!r}, "
                                 f"not {want!r}")
        if len(wave1[0]) <= eng.prefill_budget:
            raise AssertionError("no prompt exceeds the prefill budget")
        if any(f != "completed" for f in finished):
            raise AssertionError(f"unfinished requests: {finished}")
        if any(len(o) != w.new_tokens
               or not all(0 <= t < w.vocab for t in o) for o in outs):
            raise AssertionError("a request returned tokens out of range "
                                 "or the wrong count")
        if nonfinite:
            raise AssertionError(f"{nonfinite} steps returned non-finite "
                                 f"logits")
        if hits <= 0:
            raise AssertionError("the shared prefix never hit the cache")
        eng.cache.check_invariants()
        if eng.compile_counts() != warm or warm.get("mixed") != 1:
            raise AssertionError(f"serving recompiled after warm-up: "
                                 f"{warm} -> {eng.compile_counts()}")
        return info
    finally:
        eng.close()


def pool_phase(w: Widths, lm, interpret: bool, n: int) -> dict:
    """One one-chip replica per chip, over the (depth-cut) trained
    model: each owns its chip, serves seeded traffic, compiles nothing
    after warm-up."""
    from flexflow_tpu.serve import ReplicaPool, TrafficSpec, make_traffic
    from flexflow_tpu.utils.profiling import router_report

    t0 = time.perf_counter()
    pool = ReplicaPool(lm, num_replicas=n, policy="round_robin",
                       engine_kwargs={"interpret": interpret})
    try:
        boot_s = time.perf_counter() - t0
        owned = [tuple(int(d.id) for d in r.engine.devices)
                 for r in pool.replicas]
        traffic = make_traffic(TrafficSpec(
            requests=4 * n, seed=0, rate_rps=200.0, tenants=2,
            prefix_tokens=2 * w.page_size, tail_mean=8.0, output_mean=6.0,
            max_prompt=min(w.positions // 2, 96), max_new_cap=8,
            vocab=w.vocab))
        t0 = time.perf_counter()
        res = pool.run(traffic)
        run_s = time.perf_counter() - t0
        say(router_report(res, pool.metrics))
        info = {"replicas": n, "replica_devices": [list(o) for o in owned],
                "boot_s": round(boot_s, 2), "run_s": round(run_s, 2),
                "completed": res["completed"],
                "pool_devices": [device_sets(r.engine.pool)
                                 for r in pool.replicas]}
        if len(set(owned)) != n or any(len(o) != 1 for o in owned):
            raise AssertionError(f"replicas do not own distinct chips: "
                                 f"{owned}")
        if res["completed"] != len(traffic):
            raise AssertionError(f"{res['completed']} of {len(traffic)} "
                                 f"requests completed")
        pool.assert_zero_recompiles()
        pool.check_drained()
        return info
    finally:
        pool.close()


# -------------------------------------------------------------------- main
def run(widths: Widths = OPT_1_3B, *, interpret: bool = False,
        require_tpu: bool = True, max_devices: int = 0) -> int:
    """The whole smoke. The keyword arguments exist for the CPU test of
    this file's own logic at a tiny width (tests/test_chip_bringup.py:
    kernels interpreted, no TPU demanded, the first `max_devices` of the
    eight virtual devices); `python chip_smoke.py` runs the defaults."""
    import jax
    devs = jax.devices()[:max_devices or None]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if require_tpu and device["platform"] != "tpu":
        print(f"chip_smoke: no TPU — JAX found platform="
              f"{device['platform']!r} device_kind={device['kind']!r}; "
              f"nothing was run", file=sys.stderr)
        return 2
    if require_tpu:
        # the repo's own tables, before anything reaches stdout: alone
        # in a directory this fails here, with no output
        import bench
        from flexflow_tpu.parallel.mesh import MachineSpec
        bench.peak_for(device["kind"])             # SystemExit if unknown
        bench.peak_for(device["kind"], bench.PEAK_HBM_BW)
        if MachineSpec.for_device_kind(device["kind"]) is None:
            print(f"chip_smoke: no MachineSpec for {device['kind']!r}",
                  file=sys.stderr)
            return 2
    say(f"device: platform={device['platform']} "
        f"device_kind={device['kind']!r} count={device['count']}")

    from flexflow_tpu import native
    from flexflow_tpu.utils.cache_dirs import (arm_compile_cache,
                                               measurement_cache_dir)
    cache_dir, was_empty = arm_compile_cache()
    say(f"compile cache: {cache_dir} "
        f"({'empty' if was_empty else 'has entries'} at start); "
        f"measurement caches: {measurement_cache_dir()}")
    last_path = os.path.join(measurement_cache_dir(), "chip_smoke_last.json")
    try:
        with open(last_path) as f:
            previous = json.load(f)
    except (OSError, json.JSONDecodeError):
        previous = None

    clock = CompileClock()
    phases: dict = {}
    t_all = time.perf_counter()

    def phase(name, fn):
        c0, t0 = clock.seconds, time.perf_counter()
        try:
            info = fn()
        except Exception as e:   # the boundary: report, then stop
            traceback.print_exc()
            info, ok = {"error": f"{type(e).__name__}: {e}"}, False
        else:
            ok = True
        wall = time.perf_counter() - t0
        info = {"ok": ok, "wall_s": round(wall, 2),
                "compile_s": round(clock.seconds - c0, 2),
                "steady_s": round(wall - (clock.seconds - c0), 2), **info}
        phases[name] = info
        say(f"phase {name}: {'PASS' if ok else 'FAIL'} "
            f"wall={info['wall_s']}s compile={info['compile_s']}s "
            f"steady={info['steady_s']}s "
            + json.dumps({k: v for k, v in info.items()
                          if k not in ("ok", "wall_s", "compile_s",
                                       "steady_s")}))
        return ok

    def finish() -> int:
        ok = all(p["ok"] for p in phases.values())
        total = {"compile_s": round(clock.seconds, 2),
                 "compiles": clock.compiles,
                 "cache_hits": clock.cache_hits,
                 "wall_s": round(time.perf_counter() - t_all, 2)}
        say(f"compile seconds this run: {total['compile_s']} "
            f"({total['compiles']} backend compiles, "
            f"{total['cache_hits']} persistent-cache hits); previous run "
            f"in this cache: "
            f"{previous['compile_s'] if previous else 'none recorded'}")
        say(f"search engine library: {native.status()}")
        if ok:
            os.makedirs(os.path.dirname(last_path), exist_ok=True)
            with open(last_path, "w") as f:
                json.dump(total, f)
        say("details: " + json.dumps(
            {"phases": phases, "total": total,
             "previous_compile_s": (previous or {}).get("compile_s"),
             "cache_dir": cache_dir, "cache_was_empty": was_empty}))
        # the driver's contract: the last line of stdout is exactly this
        # object — "ok" and "device" (platform, kind, count), no other key
        say(json.dumps({"ok": ok, "device": device}))
        return 0 if ok else 1

    def serve(tp):
        from flexflow_tpu.config import CompMode
        gc.collect()     # the previous phase's model leaves the chips
        lm = _build_lm(widths, widths.layers, batch=1)
        lm.compile(comp_mode=CompMode.INFERENCE)
        return serve_phase(widths, lm, clock, interpret, tp)

    n = device["count"]
    steps = [("kernel", lambda: kernel_phase(widths, interpret)),
             ("train", lambda: train_phase(widths, clock, 1)),
             ("serve", lambda: serve(1))]
    if n > 1:
        # the same two phases again on every chip, and a pool of
        # one-chip replicas over the depth-cut model the trainer leaves
        trained: list = []
        steps += [(f"train_mesh{n}",
                   lambda: train_phase(widths, clock, n, trained)),
                  ("pool", lambda: pool_phase(widths, trained.pop(),
                                              interpret, n)),
                  (f"serve_tp{n}", lambda: serve(n))]
    for name, fn in steps:
        if not phase(name, fn):
            break
    return finish()


if __name__ == "__main__":
    sys.exit(run())
