"""The LFM2-MoE serving cell from start to verdict.

What lib/falconh1_cell.py does for `falcon-h1-34b-1chip-l6`, for the
configuration `lfm2-24b-a2b-1chip-l10`: build the engine from the
configuration (`build_lfm2_moe_lm` + `ServeEngine`), make the traffic
from the seed (lib/traffic_gen.make_requests: short instructions after a
tenant's prefix, long answers, Poisson arrivals), run ramp + window +
drain through lib/serving.py, then decide `correct` against
lib/reference_lfm2moe.py — and that no lane lost an expert. The loop
that keeps the emitted tokens' logits and the expert counts is
lib/olmoe_cell.py's, the comparison of one request and the verdict on
the two limits lib/phi4flash_cell.py's.

The reference holds the PUBLISHED layout; `published_params` makes it
from the system's arrays (the program keeps the convolution's
in-projection as published; its attention matrices are held a head at a
time and its dense gate and up in one): slices and reshapes, traced
under the reference's own jit so that no second copy of the weights is
ever held.

Also here, for the CPU tests and the builder's chip check
(check_lfm2moe_logits.py): `logits_through_cache`, the engine's LOGITS
through pages and tails against the reference's full forward pass at the
same positions.
"""

from __future__ import annotations

import time

import numpy as np

from . import checks, olmoe_cell, reference_lfm2moe, serving, system, \
    traffic_gen
from .olmoe_cell import emitted_logits, rms
from .phi4flash_cell import compare, verdict
from .window import Window

# the padded lengths a sequence is compared at (a compile each)
SEQ_BUCKETS = (1024, 2048)
# what a step's StepEvents counted, summed over the window's steps
STEP_COUNTS = ("grid_steps", "live_steps", "live_rows", "state_bytes",
               "full_kv_bytes", "ssm_runs", "conv_lanes", "paged_calls",
               "paged_calls_in_place")


def model_args(conf: dict) -> dict:
    """The configuration's keys as the reference's keyword arguments."""
    return {"heads": int(conf["num_attention_heads"]),
            "kv_heads": int(conf["num_key_value_heads"]),
            "experts_per_token": int(conf["num_experts_per_tok"]),
            "theta": float(conf["rope_parameters"]["rope_theta"]),
            "eps": float(conf["norm_eps"])}


def build_engine(conf: dict, seed: int, interpret: bool = False,
                 warm: bool = True):
    """The serve engine over freshly initialised weights, its one mixed
    program warmed. -> (engine, seconds spent in warmup())."""
    from flexflow_tpu.config import CompMode, FFConfig
    from flexflow_tpu.models.lfm2_moe import build_lfm2_moe_lm
    from flexflow_tpu.serve import ServeEngine
    if not (not conf["conv_bias"] and conf["norm_topk_prob"]
            and conf["use_expert_bias"]
            and conf["routed_scaling_factor"] == 1
            and conf["rope_parameters"]["rope_type"] == "default"
            and len(conf["layer_types"]) == conf["num_hidden_layers"]):
        raise SystemExit(
            "benchmark: build_lfm2_moe_lm builds the block with no "
            "convolution bias, a selection bias, renormalised top-k "
            "weights under a scaling factor of 1 and plain rotary alone")
    init = conf["init"]
    cfg = FFConfig(batch_size=1, seed=system.weight_seed(seed),
                   search_budget=0, **conf["system"])
    lm = build_lfm2_moe_lm(
        cfg, vocab_size=conf["vocab_size"],
        max_seq_len=conf["max_position_embeddings"],
        hidden=conf["hidden_size"], layer_types=conf["layer_types"],
        num_dense_layers=conf["num_dense_layers"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        ff_dim=conf["intermediate_size"], num_experts=conf["num_experts"],
        experts_per_token=conf["num_experts_per_tok"],
        expert_dim=conf["moe_intermediate_size"],
        conv_kernel=conf["conv_L_cache"],
        rope_theta=float(conf["rope_parameters"]["rope_theta"]),
        rms_eps=float(conf["norm_eps"]),
        norm_topk=bool(conf["norm_topk_prob"]),
        use_expert_bias=bool(conf["use_expert_bias"]),
        norm_init=init["norm"], final_norm_init=init["final_norm"],
        qk_norm_init=init["qk_norm"],
        tap_init=init["taps"], expert_bias_std=init["expert_bias_std"],
        stds=init["stds"])
    lm.compile(comp_mode=CompMode.INFERENCE)
    eng = ServeEngine(lm, interpret=interpret)
    t0 = time.perf_counter()
    if warm:
        eng.warmup()
    return eng, time.perf_counter() - t0


def published_params(params: dict, conf: dict) -> dict:
    """The system's parameter arrays (op name -> weight name -> array)
    in the reference's published layout. Slices and reshapes alone."""
    layers = []
    for i, kind in enumerate(conf["layer_types"]):
        layer = {"operator_norm": params[f"layer{i}_operator_norm"]["scale"],
                 "ffn_norm": params[f"layer{i}_ffn_norm"]["scale"]}
        if kind == "conv":
            c = params[f"layer{i}_conv"]
            layer.update(in_proj=c["w_in"], conv=c["conv_w"],
                         out_proj=c["w_out"])
        else:
            a = params[f"layer{i}_attn"]
            e = a["wq"].shape[0]
            layer.update(q_proj=a["wq"].reshape(e, -1),
                         k_proj=a["wk"].reshape(e, -1),
                         v_proj=a["wv"].reshape(e, -1),
                         o_proj=a["wo"].reshape(-1, e),
                         q_layernorm=a["q_norm"], k_layernorm=a["k_norm"])
        if i < int(conf["num_dense_layers"]):
            gu = params[f"layer{i}_mlp"]["w_gu"]
            f = gu.shape[1] // 2
            layer.update(w1=gu[:, :f], w3=gu[:, f:],
                         w2=params[f"layer{i}_mlp"]["w_down"])
        else:
            m = params[f"layer{i}_moe"]
            layer.update(router=m["gate"], expert_bias=m["expert_bias"],
                         w1=m["wg"], w3=m["wu"], w2=m["wd"])
        layers.append(layer)
    return {"embed": params["tok_embed"]["kernel"],
            "embedding_norm": params["embedding_norm"]["scale"],
            "layers": layers}


def reference_logits(conf: dict, **kw):
    """-> f(params, seq, rows): the reference's logits (len(rows), V)
    of the token list `seq` at positions `rows` from the SYSTEM's
    parameter arrays, the sequence padded to one of a few lengths (a
    few compiles). `kw`: the reference's arguments where they are not
    the configuration's (the controls')."""
    import jax
    import jax.numpy as jnp
    args = {**model_args(conf), **kw}

    @jax.jit
    def fn(params, tokens, rows):
        return reference_lfm2moe.logits_at(
            published_params(params, conf), tokens, rows, **args)

    positions = conf["max_position_embeddings"]

    def logits(params, seq, rows):
        n = next((b for b in SEQ_BUCKETS if len(seq) <= b <= positions),
                 positions)
        toks = np.zeros((1, n), np.int32)
        toks[0, :len(seq)] = seq
        return np.asarray(fn(params, jnp.asarray(toks), jnp.asarray(
            np.asarray(rows, np.int32))))

    return logits


def branch_sizes(params, conf: dict, seq: list) -> list:
    """The reference's root mean square of the stream and of the two
    branches (the mixer's, the feed-forward's) at every layer, over one
    sequence: what `init` is held to."""
    import jax
    import jax.numpy as jnp
    with jax.default_matmul_precision("highest"):
        _, sizes = jax.jit(lambda p, t: reference_lfm2moe.hidden_states(
            published_params(p, conf), t, sizes=True, **model_args(conf)))(
                params, jnp.asarray(np.asarray(seq, np.int32)))
    return np.asarray(sizes).round(4).tolist()


def router_readings(params, conf: dict, seq: list) -> dict:
    """What `init` is held to of the FIRST routing layer's router, over
    one sequence, on the reference: the deviation of its logits, and the
    share of tokens whose k experts chosen WITH the selection bias
    differ from those chosen without it."""
    import jax
    import jax.numpy as jnp
    R = reference_lfm2moe
    first = int(conf["num_dense_layers"])
    args = model_args(conf)
    k, eps = args["experts_per_token"], args["eps"]

    def read(p, t):
        pub = published_params(p, conf)
        lay = pub["layers"][first]
        x = _stream_before(pub, t, first, args)
        h = R._norm(x, lay["operator_norm"], eps)
        m = R._short_conv(lay, h) if "in_proj" in lay else R._attention(
            lay, h, args["heads"], args["kv_heads"], args["theta"], eps)
        h2 = R._norm(x + m, lay["ffn_norm"], eps)
        _, with_b = R.route(lay, h2, k)
        _, without = R.route(lay, h2, k, bias="none")
        differ = jnp.any(jnp.sort(with_b, axis=1)
                         != jnp.sort(without, axis=1), axis=1)
        return jnp.std(h2 @ R._f32(lay["router"])), jnp.mean(differ)

    with jax.default_matmul_precision("highest"):
        std, share = jax.jit(read)(params,
                                   jnp.asarray(np.asarray(seq, np.int32)))
    return {"router_logit_std": float(std),
            "bias_changes_choice_share": float(share)}


def _stream_before(pub: dict, tokens, layer: int, args: dict):
    """The reference's residual stream as it ENTERS `layer` (S, E)."""
    import jax.numpy as jnp
    R = reference_lfm2moe
    eps = args["eps"]
    x = R._f32(jnp.take(pub["embed"], tokens, axis=0))
    for p in pub["layers"][:layer]:
        h = R._norm(x, p["operator_norm"], eps)
        x = x + (R._short_conv(p, h) if "in_proj" in p else R._attention(
            p, h, args["heads"], args["kv_heads"], args["theta"], eps))
        h2 = R._norm(x, p["ffn_norm"], eps)
        x = x + (R._experts(p, h2, args["experts_per_token"])
                 if "router" in p else R._dense(p, h2))
    return x


# ------------------------------------------------------------- the cell
class Loop(olmoe_cell.Loop):
    """lib/olmoe_cell.py's loop (the top-k logits of the tokens every
    dispatched step emitted and its (routing layers, experts) counts),
    which also keeps what the step's StepEvents counted of its paged
    calls, its tails and its convolution lanes (`STEP_COUNTS`, its live
    lanes, its decode lanes, and whether it held a whole chunk of
    prefill lanes)."""

    def __init__(self, eng, spans):
        super().__init__(eng, spans)
        # (t_end, live, prefill lanes, decode lanes, slots in use,
        # STEP_COUNTS)
        self.count_steps = []
        step = self.session.step

        def stepped():
            ev = step()
            if ev is not None and ev.dispatched:
                self.count_steps.append((
                    time.perf_counter(),
                    ev.plan.num_prefill_lanes + ev.plan.num_decode_lanes,
                    ev.plan.num_prefill_lanes, ev.plan.num_decode_lanes,
                    len(ev.plan.chunks),
                    *(getattr(ev, key) for key in STEP_COUNTS)))
            return ev

        self.session.step = stepped


def window_step_counts(loop: Loop, w: dict, budget: int) -> dict:
    """`STEP_COUNTS` and the live lanes, summed over the window's
    steps, the median step's decode lanes and sequences in it, and the
    share of those steps that held a WHOLE chunk of `budget` prefill
    lanes: the program's own counts, made where the lanes are packed."""
    rows = [r[1:] for r in loop.count_steps if w["w0"] <= r[0] < w["w1"]]
    if not rows:
        return {}
    rows = np.asarray(rows, np.int64)
    names = ("live_lanes", "prefill_lanes", "decode_lanes", "seqs_in_step")
    out = dict(zip(names + STEP_COUNTS, map(int, rows.sum(axis=0))))
    del out["seqs_in_step"]
    out["decode_lanes_p50"] = float(np.median(rows[:, 2]))
    out["seqs_in_step_p50"] = float(np.median(rows[:, 3]))
    out["seqs_in_step_max"] = int(rows[:, 3].max())
    out["whole_chunk_step_share"] = float(np.mean(rows[:, 1] >= budget))
    out["decode_only_step_share"] = float(np.mean(rows[:, 1] == 0))
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
        "expert_impl": eng.arch.expert_impl(eng.mixed_width),
        "kinds": "".join(eng.arch.mixer(i)[0]
                         for i in range(eng.num_layers)),
        "dense_layers": eng.arch.dense_layers,
        "experts": eng.arch.experts,
        "experts_per_token": eng.arch.experts_per_token,
        **{k: v for k, v in eng.boot_stats.items()
           if k.startswith("conv_tail")},
        "layers": eng.num_layers,
        "cache_bytes_per_token": c.cache_bytes_per_token,
        "cache_bytes_per_seq": c.constant_bytes_per_seq,
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
               cache_bytes_per_seq=stats["cache_bytes_constant_per_seq"],
               # the engine's own total over the whole session
               expert_dropped=stats["experts"]["dropped"],
               **olmoe_cell.window_expert_numbers(loop, w, {
                   "hidden_size": conf["hidden_size"],
                   "intermediate_size": conf["moe_intermediate_size"]}),
               **window_step_counts(loop, w, eng.prefill_budget))

    # ---- correct: outside the window, its seconds on a line of its own
    t0 = time.perf_counter()
    chk = conf["check"]
    why = []
    try:
        eng.cache.check_invariants(eng.pool)
    except AssertionError as e:
        why.append(f"cache invariants: {e}")
    # the reference walks a sequence beside the 9.8 GiB of weights: the
    # pool's pages and tails have served, and make room for it
    eng.pool = None
    picks = checks.pick_requests(loop.check_records(), eng.prefill_budget,
                                 ctx.seed, int(chk["requests"]))
    found = check_serving(eng.params, conf, picks, int(t["output"]["max"]),
                          loop.top_logits)
    why = verdict(found, chk) + why
    if num["expert_dropped"]:
        why.append(f"{num['expert_dropped']} expert slots of live lanes "
                   f"reached no expert")
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
                         on_step=None, reference=None) -> tuple:
    """lib/falconh1_cell.logits_through_cache against this model's
    reference (`reference`: one made already, so that several engines
    over the same weights share its compiles): serve `groups` one after
    another through ONE session, the prompts of a group TOGETHER,
    greedy, and compare the engine's top-k logits at every generated
    position with the reference's full forward pass over prompt +
    generated tokens. `on_step(session, event)` runs after every step.
    -> (one dict a prompt, the session's stats_dict())."""
    reference = reference or reference_logits(conf)
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
