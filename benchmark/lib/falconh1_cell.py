"""The Falcon-H1 serving cell from start to verdict.

What lib/olmohybrid_cell.py does for `olmo-hybrid-7b-1chip-l16`, for the
configuration `falcon-h1-34b-1chip-l6`: build the engine from the
configuration (`build_falcon_h1_lm` + `ServeEngine`), make the traffic
from the seed (lib/traffic_gen.make_requests: short chat turns after a
tenant's system prompt, arrivals in bursts), run ramp + window + drain
through lib/serving.py, then decide `correct` against
lib/reference_falconh1.py. The loop that keeps the emitted tokens'
logits is lib/olmoe_cell.py's, the comparison of one request and the
verdict on the two limits lib/phi4flash_cell.py's.

The reference holds the PUBLISHED layout, one matrix a projection;
`published_params` makes it from the system's arrays (the program keeps
the published in-projection as it is; its attention matrices are held a
head at a time and its feed-forward gate and up in one): slices and
reshapes, traced under the reference's own jit so that no second copy of
the weights is ever held.

Also here, for the CPU tests and the builder's chip check
(check_falconh1_logits.py): `logits_through_cache`, the engine's LOGITS
through pages, state slots and tails against the reference's full
forward pass at the same positions.
"""

from __future__ import annotations

import time

import numpy as np

from . import checks, olmoe_cell, reference_falconh1, serving, system, \
    traffic_gen
from .olmoe_cell import emitted_logits, rms
from .phi4flash_cell import compare, verdict
from .window import Window

# the padded lengths a sequence is compared at (a compile each)
SEQ_BUCKETS = (768, 1536, 3200)
# what a step's StepEvents counted, summed over the window's steps
STEP_COUNTS = ("grid_steps", "live_steps", "live_rows", "state_bytes",
               "full_kv_bytes", "ssm_runs", "ssd_lanes", "ssd_chunk_blocks",
               "paged_calls", "paged_calls_in_place")
MULTIPLIERS = ("embedding_multiplier", "lm_head_multiplier",
               "ssm_in_multiplier", "ssm_multipliers", "ssm_out_multiplier",
               "attention_in_multiplier", "attention_out_multiplier",
               "key_multiplier", "mlp_multipliers")


def multipliers(conf: dict) -> dict:
    """The configuration's muP scalars by their published keys."""
    return {k: tuple(map(float, conf[k])) if isinstance(conf[k], list)
            else float(conf[k]) for k in MULTIPLIERS}


def model_args(conf: dict) -> dict:
    """The configuration's keys as the reference's keyword arguments."""
    return {"mult": multipliers(conf),
            "heads": int(conf["num_attention_heads"]),
            "kv_heads": int(conf["num_key_value_heads"]),
            "ssm_heads": int(conf["mamba_n_heads"]),
            "groups": int(conf["mamba_n_groups"]),
            "d_state": int(conf["mamba_d_state"]),
            "theta": float(conf["rope_theta"]),
            "eps": float(conf["rms_norm_eps"])}


def build_engine(conf: dict, seed: int, interpret: bool = False,
                 warm: bool = True):
    """The serve engine over freshly initialised weights, its one mixed
    program warmed. -> (engine, seconds spent in warmup())."""
    from flexflow_tpu.config import CompMode, FFConfig
    from flexflow_tpu.models.falcon_h1 import build_falcon_h1_lm
    from flexflow_tpu.serve import ServeEngine
    if not (conf["mamba_rms_norm"] and not conf["mamba_norm_before_gate"]
            and conf["mamba_conv_bias"] and not conf["mamba_proj_bias"]
            and not conf["attention_bias"] and not conf["mlp_bias"]
            and not conf["projectors_bias"]
            and not conf["tie_word_embeddings"]
            and conf["rope_scaling"] is None
            and conf["hidden_act"] == "silu"
            and conf["mamba_d_ssm"]
            == conf["mamba_n_heads"] * conf["mamba_d_head"]):
        raise SystemExit(
            "benchmark: build_falcon_h1_lm builds the untied block with "
            "the gated per-group norm after the gate, a convolution "
            "bias, silu and no other bias alone")
    init = conf["init"]
    cfg = FFConfig(batch_size=1, seed=system.weight_seed(seed),
                   search_budget=0, **conf["system"])
    lm = build_falcon_h1_lm(
        cfg, vocab_size=conf["vocab_size"],
        max_seq_len=conf["max_position_embeddings"],
        hidden=conf["hidden_size"], num_layers=conf["num_hidden_layers"],
        num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"], ff_dim=conf["intermediate_size"],
        rope_theta=float(conf["rope_theta"]),
        ssm_heads=conf["mamba_n_heads"], ssm_head_dim=conf["mamba_d_head"],
        ssm_groups=conf["mamba_n_groups"], ssm_state=conf["mamba_d_state"],
        conv_kernel=conf["mamba_d_conv"],
        rms_eps=float(conf["rms_norm_eps"]), **multipliers(conf),
        norm_init=init["norm"], dt_range=init["dt"], a_range=init["a"],
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
    for i in range(int(conf["num_hidden_layers"])):
        s, a = params[f"layer{i}_ssm"], params[f"layer{i}_attn"]
        gu = params[f"layer{i}_mlp"]["w_gu"]
        f = gu.shape[1] // 2
        e = a["wq"].shape[0]
        layers.append({
            "input_norm": params[f"layer{i}_ln"]["scale"],
            "pre_ff_norm": params[f"layer{i}_ln2"]["scale"],
            "in_proj": s["w_in"], "conv": s["conv_w"],
            "conv_bias": s["conv_b"], "A_log": s["A_log"], "D": s["D"],
            "dt_bias": s["dt_bias"], "ssm_norm": s["norm"],
            "out_proj": s["w_out"],
            "q_proj": a["wq"].reshape(e, -1),
            "k_proj": a["wk"].reshape(e, -1),
            "v_proj": a["wv"].reshape(e, -1),
            "o_proj": a["wo"].reshape(-1, e),
            "gate_proj": gu[:, :f], "up_proj": gu[:, f:],
            "down_proj": params[f"layer{i}_mlp"]["w_down"]})
    return {"embed": params["tok_embed"]["kernel"],
            "final_norm": params["final_norm"]["scale"],
            "lm_head": params["lm_head"]["kernel"], "layers": layers}


def reference_logits(conf: dict, **kw):
    """-> f(params, seq, rows): the reference's logits (len(rows), V)
    of the token list `seq` at positions `rows` from the SYSTEM's
    parameter arrays, the sequence padded to one of a few lengths (a
    few compiles). `kw`: the reference's arguments where they are not
    the configuration's."""
    import jax
    import jax.numpy as jnp
    args = {**model_args(conf), **kw}
    mult = args.pop("mult")

    @jax.jit
    def fn(params, tokens, rows):
        return reference_falconh1.logits_at(
            published_params(params, conf), tokens, rows, mult, **args)

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
    """The reference's root mean square of the stream and of the three
    branches (SSM, attention, feed-forward) at every layer, over one
    sequence: what `init` is held to."""
    import jax
    import jax.numpy as jnp
    args = model_args(conf)
    mult = args.pop("mult")
    with jax.default_matmul_precision("highest"):
        _, sizes = jax.jit(lambda p, t: reference_falconh1.hidden_states(
            published_params(p, conf), t, mult, sizes=True, **args))(
                params, jnp.asarray(np.asarray(seq, np.int32)))
    return np.asarray(sizes).round(4).tolist()


# ------------------------------------------------------------- the cell
class Loop(olmoe_cell.Loop):
    """lib/olmoe_cell.py's loop (the top-k logits of the tokens every
    dispatched step emitted), which also keeps what the step's
    StepEvents counted of its paged calls, its states and the
    recurrence's two forms (`STEP_COUNTS`, its live lanes, and whether
    it held a whole chunk of prefill lanes)."""

    def __init__(self, eng, spans):
        super().__init__(eng, spans)
        self.count_steps = []   # (t_end, live, prefill lanes, STEP_COUNTS)
        step = self.session.step

        def stepped():
            ev = step()
            if ev is not None and ev.dispatched:
                self.count_steps.append((
                    time.perf_counter(),
                    ev.plan.num_prefill_lanes + ev.plan.num_decode_lanes,
                    ev.plan.num_prefill_lanes,
                    *(getattr(ev, key) for key in STEP_COUNTS)))
            return ev

        self.session.step = stepped


def window_step_counts(loop: Loop, w: dict, budget: int) -> dict:
    """`STEP_COUNTS` and the live lanes, summed over the window's
    steps, and the share of those steps that held a WHOLE chunk of
    `budget` prefill lanes: the program's own counts, made where the
    lanes are packed."""
    rows = [r[1:] for r in loop.count_steps if w["w0"] <= r[0] < w["w1"]]
    if not rows:
        return {}
    rows = np.asarray(rows, np.int64)
    out = dict(zip(("live_lanes", "prefill_lanes") + STEP_COUNTS,
                   map(int, rows.sum(axis=0))))
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
        "attn_impl": eng.attn_impl, "scan_impl": eng.scan_impl,
        **{k: v for k, v in eng.boot_stats.items()
           if k.startswith("ssd_state")},
        "layers": eng.num_layers,
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
    # pool's pages and states have served, and make room for it
    eng.pool = None
    picks = checks.pick_requests(loop.check_records(), eng.prefill_budget,
                                 ctx.seed, int(chk["requests"]))
    found = check_serving(eng.params, conf, picks, int(t["output"]["max"]),
                          loop.top_logits)
    why = verdict(found, chk) + why
    if stats["nonfinite_logit_steps"]:
        why.append(f"{stats['nonfinite_logit_steps']} steps with "
                   f"non-finite logits")
    if eng.attn_impl != system.expected_attn_impl(ctx.rehearse):
        why.append(f"attention ran as {eng.attn_impl!r}")
    if not ctx.rehearse and eng.scan_impl != eng.attn_impl:
        why.append(f"the Mamba-2 lanes ran as {eng.scan_impl!r}")
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
    """lib/olmohybrid_cell.logits_through_cache against this model's
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
