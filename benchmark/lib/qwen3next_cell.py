"""The Qwen3-Next serving cell from start to verdict.

What lib/cmdaplus_cell.py does for `command-a-plus-1chip-ep8` and
lib/sala_cell.py for `minicpm-sala-1chip-l16`, for the configuration
`qwen3-next-80b-a3b-1chip-ep4-l8`: build the engine from the
configuration (`build_qwen3_next_lm` + `ServeEngine`), make the traffic
from the seed (lib/traffic_gen.make_requests: one class of long prompts
after a tenant's system prompt), run ramp + window + drain through
lib/serving.py, then decide `correct` against
lib/reference_qwen3next.py — and that no live lane lost a held expert.
The loop that keeps the emitted tokens' logits and the expert numbers
of the window are lib/olmoe_cell.py's, the comparison of one request
and the verdict on the two limits lib/phi4flash_cell.py's.

Also here, for the CPU tests and the builder's chip check
(check_qwen3next_logits.py): `logits_through_cache`, the engine's LOGITS
through pages, state slots and tails against the reference's full
forward pass at the same positions.
"""

from __future__ import annotations

import functools
import time

import numpy as np

from . import checks, olmoe_cell, reference_qwen3next, serving, system, \
    traffic_gen
from .olmoe_cell import emitted_logits, rms, window_expert_numbers
from .phi4flash_cell import compare, verdict
from .window import Window

# the padded lengths a sequence is compared at (a compile each)
SEQ_BUCKETS = (2048, 4096, 8192, 12288, 16384, 20480, 27136)
# what a step's StepEvents counted, summed over the window's steps
STEP_COUNTS = ("grid_steps", "live_steps", "live_rows", "expert_slots",
               "slots_held", "state_bytes", "full_kv_bytes")


def held(conf: dict) -> tuple:
    """(first, count) of the experts this chip holds."""
    return (int(conf.get("experts_first", 0)), int(conf["num_experts"]))


def model_args(conf: dict) -> dict:
    """The configuration's keys as the reference's keyword arguments."""
    return {"num_layers": int(conf["num_hidden_layers"]),
            "interval": int(conf["full_attention_interval"]),
            "theta": float(conf["rope_theta"]),
            "rotary_dim": int(conf["head_dim"]
                              * conf["partial_rotary_factor"]),
            "key_heads": int(conf["linear_num_key_heads"]),
            "ratio": conf["linear_num_value_heads"]
            // conf["linear_num_key_heads"],
            "experts_per_token": int(conf["num_experts_per_tok"]),
            "held": held(conf), "eps": float(conf["rms_norm_eps"]),
            # the router's operands are the block's activations, in the
            # precision the configuration states for them (`assumed`)
            "router_dtype": conf["system"]["compute_dtype"]}


def build_engine(conf: dict, seed: int, interpret: bool = False,
                 warm: bool = True):
    """The serve engine over freshly initialised weights, its one mixed
    program warmed. -> (engine, seconds spent in warmup())."""
    from flexflow_tpu.config import CompMode, FFConfig
    from flexflow_tpu.models.qwen3_next import build_qwen3_next_lm
    from flexflow_tpu.serve import ServeEngine
    if not (conf["norm_topk_prob"] and conf["decoder_sparse_step"] == 1
            and not conf["mlp_only_layers"]
            and not conf["tie_word_embeddings"]
            and not conf["use_sliding_window"]
            and conf["rope_scaling"] is None
            and conf["hidden_act"] == "silu"):
        raise SystemExit(
            "benchmark: build_qwen3_next_lm builds the untied block with "
            "an expert layer in every layer, renormalised top-k, silu, no "
            "window and no rotary scaling alone")
    init = conf["init"]
    cfg = FFConfig(batch_size=1, seed=system.weight_seed(seed),
                   search_budget=0, **conf["system"])
    lm = build_qwen3_next_lm(
        cfg, vocab_size=conf["vocab_size"],
        max_seq_len=conf["max_position_embeddings"],
        hidden=conf["hidden_size"], num_layers=conf["num_hidden_layers"],
        full_attention_interval=conf["full_attention_interval"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"],
        partial_rotary_factor=float(conf["partial_rotary_factor"]),
        rope_theta=float(conf["rope_theta"]),
        key_heads=conf["linear_num_key_heads"],
        value_heads=conf["linear_num_value_heads"],
        key_dim=conf["linear_key_head_dim"],
        value_dim=conf["linear_value_head_dim"],
        conv_kernel=conf["linear_conv_kernel_dim"],
        num_experts=conf["router_width"],
        experts_per_token=conf["num_experts_per_tok"],
        expert_dim=conf["moe_intermediate_size"],
        shared_expert_dim=conf["shared_expert_intermediate_size"],
        experts_held=held(conf), rms_eps=float(conf["rms_norm_eps"]),
        norm_init=init["norm"], qk_norm_init=init["qk_norm"],
        delta_norm_init=init["delta_norm"], dt_range=init["dt"],
        init_std=float(init["matrix_std"]))
    lm.compile(comp_mode=CompMode.INFERENCE)
    eng = ServeEngine(lm, interpret=interpret)
    t0 = time.perf_counter()
    if warm:
        eng.warmup()
    return eng, time.perf_counter() - t0


def reference_logits(conf: dict, **kw):
    """-> f(params, seq, rows): the reference's logits (len(rows), V)
    of the token list `seq` at positions `rows`, the sequence padded to
    one of a few lengths (a few compiles). `kw`: the reference's
    arguments where they are not the configuration's (`router_dtype`)."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(functools.partial(
        reference_qwen3next.logits_at, **{**model_args(conf), **kw}))
    positions = conf["max_position_embeddings"]

    def logits(params, seq, rows):
        n = next((b for b in SEQ_BUCKETS if len(seq) <= b <= positions),
                 positions)
        toks = np.zeros((1, n), np.int32)
        toks[0, :len(seq)] = seq
        return np.asarray(fn(params, jnp.asarray(toks), jnp.asarray(
            np.asarray(rows, np.int32))))

    return logits


# ------------------------------------------------------------- the cell
class Loop(olmoe_cell.Loop):
    """lib/olmoe_cell.py's loop (every dispatched step's (layers, held)
    expert counts, and the top-k logits of the tokens it emitted),
    which also keeps what the step's StepEvents counted of its paged
    calls, its experts and its states (`STEP_COUNTS`, and its live
    lanes)."""

    def __init__(self, eng, spans):
        super().__init__(eng, spans)
        self.count_steps = []       # (t_end, live lanes, STEP_COUNTS)
        step = self.session.step

        def stepped():
            ev = step()
            if ev is not None and ev.dispatched:
                self.count_steps.append((
                    time.perf_counter(),
                    ev.plan.num_prefill_lanes + ev.plan.num_decode_lanes,
                    *(getattr(ev, key) for key in STEP_COUNTS)))
            return ev

        self.session.step = stepped


def window_step_counts(loop: Loop, w: dict) -> dict:
    """`STEP_COUNTS` and the live lanes, summed over the window's
    steps: the program's own counts, made where the lanes are packed
    and where the expert counts are fetched."""
    rows = [r[1:] for r in loop.count_steps if w["w0"] <= r[0] < w["w1"]]
    if not rows:
        return {}
    total = np.sum(np.asarray(rows, np.int64), axis=0)
    out = dict(zip(("live_lanes",) + STEP_COUNTS, map(int, total)))
    out["slots_routed"] = out.pop("expert_slots")
    return out


def check_serving(params, conf: dict, picks: list, max_new: int,
                  top_logits) -> dict:
    """lib/phi4flash_cell.check_serving against this model's reference:
    the logits the window's own steps emitted for the sampled requests
    (`top_logits(rid)`), every generated position of each."""
    reference = reference_logits(conf)
    rows = [compare(reference, params, r["prompt"], r["tokens"],
                    *top_logits(r["rid"]), rows_padded=max_new)
            for r in picks]
    errs = [r.pop("errors") for r in rows]
    return {"worst_gap": max((r["worst_gap"] for r in rows), default=None),
            "logit_rms_err": rms(errs) if errs else None,
            "logit_max_abs_err": max((r["logit_abs_err"] for r in rows),
                                     default=None),
            "argmax_agree": sum(r["argmax_agree"] for r in rows),
            "positions": sum(r["new"] for r in rows), "requests": rows}


def run(ctx) -> dict:
    conf, t = ctx.conf, ctx.traffic
    eng, warmup_s = build_engine(conf, ctx.seed, ctx.rehearse)
    c = eng.cache_cfg
    ctx.say("engine", {
        "arch": eng.arch.kind, "lanes": eng.mixed_width,
        "max_seqs": c.max_seqs, "pages": c.num_pages,
        "attn_impl": eng.attn_impl,
        "delta_impl": eng.geometry.delta_impl,
        "expert_impl": eng.expert_impl, "layers": eng.num_layers,
        "kinds": "".join(k[0] for k in eng.arch.kinds),
        "experts": eng.arch.experts, "experts_held": eng.arch.experts_held,
        "experts_per_token": eng.arch.experts_per_token,
        "cache_bytes_per_token": c.cache_bytes_per_token,
        "cache_bytes_constant_per_seq": c.constant_bytes_per_seq,
        "pool_bytes": c.pool_bytes, "warmup_s": warmup_s,
        "spec_tokens": eng.spec_tokens})
    t0 = time.perf_counter()
    reqs = traffic_gen.make_requests(t, ctx.seed, conf["vocab_size"],
                                     int(t["pool_requests"]))
    ctx.say("traffic", {"requests_made": len(reqs),
                        "make_s": time.perf_counter() - t0,
                        "prompt_tokens_mean":
                            sum(len(r.prompt) for r in reqs) / len(reqs),
                        "max_new_mean":
                            sum(r.max_new for r in reqs) / len(reqs)})
    loop = Loop(eng, ctx.spans)
    win = Window(ctx.spans, eng.compile_counts, ctx.trace_dir,
                 float(t.get("trace_s", 5.0)))
    ramp, drain = float(t["ramp_s"]), float(t["drain_s"])
    ctx.chip.take("before_ramp")
    w = serving.run_open_loop(loop, reqs, ramp, ctx.seconds, drain,
                              win.tick)
    ctx.chip.take("after_drain")    # before the profiler stops
    trace = win.finish(ctx.chips)
    stats = loop.close()
    num = serving.window_numbers(loop, w, True)
    num.update(setup_s=win.t_open - ctx.t_process_start,
               compiles_in_window=win.compiles_in_window(),
               drain_s=w["t_end"] - w["w1"],
               nonfinite_logit_steps=stats["nonfinite_logit_steps"],
               preemptions=stats["preemptions"],
               rejected=stats["rejected"],
               cache_bytes_per_token=stats["cache_bytes_per_token"],
               cache_bytes_constant_per_seq=stats[
                   "cache_bytes_constant_per_seq"],
               # the engine's own totals over the whole session
               expert_dropped=stats["experts"]["dropped"],
               **window_expert_numbers(loop, w, {
                   "hidden_size": conf["hidden_size"],
                   "intermediate_size": conf["moe_intermediate_size"]}),
               **window_step_counts(loop, w))
    num.pop("expert_slots", None)   # the held slots: `slots_held` above

    # ---- correct: outside the window, its seconds on a line of its own
    t0 = time.perf_counter()
    chk = conf["check"]
    why = []
    try:
        eng.cache.check_invariants(eng.pool)
    except AssertionError as e:
        why.append(f"cache invariants: {e}")
    # the reference walks a 27k-token sequence beside the weights: the
    # pool's pages and states have served, and make room for it
    eng.pool = None
    picks = checks.pick_requests(loop.check_records(), eng.prefill_budget,
                                 ctx.seed, int(chk["requests"]))
    found = check_serving(eng.params, conf, picks, int(t["output"]["max"]),
                          loop.top_logits)
    why = verdict(found, chk) + why
    if num["expert_dropped"]:
        why.append(f"{num['expert_dropped']} expert slots of live lanes "
                   f"reached neither a held expert nor the absent count")
    if stats["nonfinite_logit_steps"]:
        why.append(f"{stats['nonfinite_logit_steps']} steps with "
                   f"non-finite logits")
    if eng.attn_impl != system.expected_attn_impl(ctx.rehearse):
        why.append(f"attention ran as {eng.attn_impl!r}")
    if num["failed"]:
        why.append(f"{num['failed']} of {num['attempted']} requests not "
                   f"completed after a drain of {drain:g} s")
    if num["compiles_in_window"]:
        why.append(f"{num['compiles_in_window']} compiles in the window")
    ctx.say("check", {**found, "check_s": time.perf_counter() - t0,
                      "why_incorrect": why})
    eng.close()
    return {"numbers": num, "trace": trace, "correct": not why,
            "attempted": num["attempted"], "failed": num["failed"]}


# ------------------------------------- logits through the cache (checks)
def logits_through_cache(eng, conf: dict, groups: list, max_new: int,
                         on_step=None) -> tuple:
    """lib/cmdaplus_cell.logits_through_cache against this model's
    reference: serve `groups` one after another through ONE session,
    the prompts of a group TOGETHER, greedy, and compare the engine's
    top-k logits at every generated position with the reference's full
    forward pass over prompt + generated tokens. `on_step(session,
    event)` runs after every step.
    -> (one dict a prompt, the session's stats_dict())."""
    reference = reference_logits(conf)
    out = []
    session = eng.start_session()
    for prompts in groups:
        reqs = [session.submit(p, max_new) for p in prompts]
        tops = {r.rid: [] for r in reqs}
        chunks = {r.rid: 0 for r in reqs}
        while session.has_work():
            ev = session.step()
            if ev is None:
                break
            for ch in (ev.plan.chunks if ev.plan else ()):
                chunks[ch.req.rid] += not ch.is_decode
            if ev.dispatched and ev.emitted:
                rids, v, i = emitted_logits(ev)
                for j, rid in enumerate(rids):
                    tops[rid].append((v[j], i[j]))
            if on_step is not None:
                on_step(session, ev)
        for req, prompt in zip(reqs, prompts):
            row = compare(reference, eng.params, prompt, req.out_tokens,
                          np.stack([v for v, _ in tops[req.rid]]),
                          np.stack([i for _, i in tops[req.rid]]))
            row.update(prefill_chunks=chunks[req.rid], together=len(reqs))
            out.append(row)
    stats = session.stats_dict()
    session.close()
    return out, stats
