"""The OLMoE serving cell from start to verdict.

What lib/serve_cell.py does for the OPT cells, for the configuration
`olmoe-1b-7b-1chip`: build the engine from the configuration
(`build_olmoe_lm` + `ServeEngine`), make the traffic from the seed, run
ramp + window + drain through lib/serving.py, then decide `correct`
against lib/reference_olmoe.py — and that no lane lost an expert.
lib/system.py and lib/checks.py name the OPT builder and reference, so
this module stands beside them (PERF.md section 7: fold both behind an
`architecture` key of the configuration).

Also here, for the CPU tests and the builder's chip check
(check_olmoe_logits.py): the engine's LOGITS through the paged cache
against the reference's full forward pass at the same positions.
"""

from __future__ import annotations

import functools
import time

import numpy as np

from . import (checks, moe_counts, reference_olmoe, serving, system,
               traffic_gen)
from .window import Window


def model_args(conf: dict) -> dict:
    """The configuration's keys as the reference's keyword arguments."""
    return {"experts_per_token": int(conf["num_experts_per_tok"]),
            "rope_theta": float(conf["rope_theta"]),
            "rms_eps": float(conf["rms_norm_eps"])}


def build_engine(conf: dict, seed: int, interpret: bool = False):
    """The serve engine over freshly initialised weights, its one mixed
    program warmed. -> (engine, seconds spent in warmup())."""
    from flexflow_tpu.config import CompMode, FFConfig
    from flexflow_tpu.models.olmoe import build_olmoe_lm
    from flexflow_tpu.serve import ServeEngine
    cfg = FFConfig(batch_size=1, seed=system.weight_seed(seed),
                   search_budget=0, **conf["system"])
    lm = build_olmoe_lm(
        cfg, vocab_size=conf["vocab_size"],
        max_seq_len=conf["max_position_embeddings"],
        hidden=conf["hidden_size"], num_heads=conf["num_attention_heads"],
        num_layers=conf["num_hidden_layers"],
        num_experts=conf["num_experts"],
        experts_per_token=conf["num_experts_per_tok"],
        expert_dim=conf["intermediate_size"],
        rope_theta=float(conf["rope_theta"]),
        rms_eps=float(conf["rms_norm_eps"]),
        norm_topk=bool(conf["norm_topk_prob"]))
    lm.compile(comp_mode=CompMode.INFERENCE)
    eng = ServeEngine(lm, interpret=interpret)
    t0 = time.perf_counter()
    eng.warmup()
    return eng, time.perf_counter() - t0


def reference_logits(conf: dict):
    """-> f(params, seq, rows): the reference's logits (len(rows), V)
    of the token list `seq` at positions `rows`, the sequence padded to
    one of a few lengths (lib/checks.py's buckets: a few compiles)."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(functools.partial(
        reference_olmoe.logits_at, num_layers=conf["num_hidden_layers"],
        **model_args(conf)))

    def logits(params, seq, rows):
        toks = np.zeros((1, checks._bucket(
            len(seq), conf["max_position_embeddings"])), np.int32)
        toks[0, :len(seq)] = seq
        return np.asarray(fn(params, jnp.asarray(toks), jnp.asarray(
            np.asarray(rows, np.int32))))

    return logits


def emitted_logits(ev) -> tuple:
    """-> (request ids, top-k logits, their token ids), one row for
    every token the step emitted, in order: the emitting lanes' rows of
    the step's two fetched arrays, one slice each."""
    rids, lanes = [], []
    for (req, n), lane in zip(ev.emitted, ev.emit_lanes):
        rids += [req.rid] * n
        lanes += range(lane, lane + n)
    return rids, ev.topv[lanes], ev.topi[lanes]


class Loop(serving.ServeLoop):
    """lib/serving.py's loop, which also keeps what the engine's step
    events say of the expert layer and of the logits: per dispatched
    step its end time and (layers, experts) slot counts, and the top-k
    logits of the tokens it emitted (`top_logits` sorts them by
    request after the window, for the few the check picks)."""

    def __init__(self, eng, spans):
        super().__init__(eng, spans)
        self.expert_steps = []      # (t_end, counts (layers, experts))
        self.logit_steps = []       # emitted_logits() of every step
        step = self.session.step

        def stepped():
            ev = step()
            if ev is not None and ev.dispatched:
                if ev.expert_counts is not None:
                    self.expert_steps.append(
                        (time.perf_counter(), ev.expert_counts))
                if ev.emitted:
                    self.logit_steps.append(emitted_logits(ev))
            return ev

        self.session.step = stepped

    def check_records(self):
        recs = super().check_records()
        for rec, r in zip(recs, self.records):
            rec["rid"] = r.handle.rid if r.handle else None
        return recs

    def top_logits(self, rid) -> tuple:
        """-> (values (tokens, k), token ids (tokens, k)) of every
        token request `rid` was given, in order."""
        rows = [(v[j], i[j]) for rids, v, i in self.logit_steps
                for j, r in enumerate(rids) if r == rid]
        return (np.stack([v for v, _ in rows]),
                np.stack([i for _, i in rows]))


def logit_errors(topv, topi, logits) -> np.ndarray:
    """The engine's top-k logits (tokens, k) minus the reference's
    logits (tokens, V) of the same tokens."""
    return np.asarray(topv, np.float64) - np.take_along_axis(
        np.asarray(logits, np.float64), np.asarray(topi, np.int64), axis=1)


def rms(errors) -> float:
    errors = np.concatenate([np.ravel(e) for e in errors])
    return float(np.sqrt(np.mean(np.square(errors))))


def check_serving(params, conf: dict, picks: list, max_new: int,
                  top_logits) -> dict:
    """lib/checks.check_serving against the OLMoE reference: at every
    generated position the reference's largest logit minus its logit of
    the token the engine chose (0 where they agree) — and the engine's
    own top-k logits (through the cache; `top_logits(rid)`) minus the
    reference's logits of those tokens: their root mean square over
    every checked position (what `logit_rms` limits: a mean keeps the
    arithmetic's noise and drops the ties) and the largest."""
    ref = reference_logits(conf)
    gaps, errs, agree, total, rows_out = [], [], 0, 0, []
    for r in picks:
        n_p, n_g = len(r["prompt"]), len(r["tokens"])
        rows = np.zeros((max_new,), np.int32)   # one length: one compile
        rows[:n_g] = np.arange(n_p - 1, n_p - 1 + n_g)
        logits = ref(params, list(r["prompt"]) + list(r["tokens"]),
                     rows)[:n_g]
        chosen = logits[np.arange(n_g), np.asarray(r["tokens"])]
        gap = logits.max(axis=1) - chosen
        topv, topi = top_logits(r["rid"])
        err = logit_errors(topv[:n_g], topi[:n_g], logits)
        gaps.append(float(gap.max()))
        errs.append(err)
        agree += int((gap == 0).sum())
        total += n_g
        rows_out.append({"prompt": n_p, "new": n_g,
                         "hit_tokens": r["hit_tokens"],
                         "worst_gap": float(gap.max()),
                         "logit_abs_err": float(np.abs(err).max()),
                         "logit_rms_err": rms([err]),
                         "logit_std": float(logits.std())})
    return {"worst_gap": max(gaps) if gaps else None,
            "logit_rms_err": rms(errs) if errs else None,
            "logit_max_abs_err": max(
                r["logit_abs_err"] for r in rows_out) if errs else None,
            "argmax_agree": agree, "positions": total, "requests": rows_out}


def window_expert_numbers(loop: Loop, w: dict, conf: dict) -> dict:
    """The expert layer over the window's steps: slots routed, the
    largest per-expert count of a layer over that layer's mean, the
    (layer, expert) pairs touched a step, the operations and bytes the
    counts stand for (lib/moe_counts.py)."""
    steps = [c for t, c in loop.expert_steps if w["w0"] <= t < w["w1"]]
    if not steps:
        return {}
    total = np.sum(steps, axis=0)
    work = [moe_counts.step_work(
        c, conf["hidden_size"], conf["intermediate_size"], 2)
        for c in steps]
    return {
        "expert_slots": int(total.sum()),
        "expert_load_max_over_mean": float(
            (total.max(axis=1) / total.mean(axis=1)).max()),
        "experts_touched_mean": float(np.mean(
            [w_["touched"] for w_ in work])),
        "expert_flops": float(sum(w_["flops"] for w_ in work)),
        "expert_weight_bytes": float(sum(w_["weight_bytes"] for w_ in work)),
    }


def run(ctx) -> dict:
    conf, t = ctx.conf, ctx.traffic
    eng, warmup_s = build_engine(conf, ctx.seed, ctx.rehearse)
    ctx.say("engine", {
        "arch": eng.arch.kind, "lanes": eng.mixed_width,
        "max_seqs": eng.cache_cfg.max_seqs,
        "pages": eng.cache_cfg.num_pages, "attn_impl": eng.attn_impl,
        "layers": eng.num_layers, "experts": eng.arch.experts,
        "experts_per_token": eng.arch.experts_per_token,
        "warmup_s": warmup_s, "spec_tokens": eng.spec_tokens})
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
               # the engine's own total over the whole session
               expert_dropped=stats["experts"]["dropped"],
               **window_expert_numbers(loop, w, conf))

    # ---- correct: outside the window, its seconds on a line of its own
    t0 = time.perf_counter()
    why = []
    chk = conf["check"]
    picks = checks.pick_requests(loop.check_records(), eng.prefill_budget,
                                 ctx.seed, int(chk["requests"]))
    found = check_serving(eng.params, conf, picks, int(t["output"]["max"]),
                          loop.top_logits)
    if found["worst_gap"] is None:
        why.append("no completed request to compare")
    else:
        if not found["worst_gap"] <= chk["logit_margin"]:
            why.append(
                f"the engine chose a token {found['worst_gap']:.4g} "
                f"below the reference's best (> {chk['logit_margin']})")
        if not found["logit_rms_err"] <= chk["logit_rms"]:
            why.append(
                f"the engine's logits differ from the reference's by "
                f"{found['logit_rms_err']:.4g} in the root mean square "
                f"(> {chk['logit_rms']})")
    if num["expert_dropped"]:
        why.append(f"{num['expert_dropped']} expert slots of live lanes "
                   f"reached no expert")
    if stats["nonfinite_logit_steps"]:
        why.append(f"{stats['nonfinite_logit_steps']} steps with "
                   f"non-finite logits")
    if eng.attn_impl != system.expected_attn_impl(ctx.rehearse):
        why.append(f"attention ran as {eng.attn_impl!r}")
    try:
        eng.cache.check_invariants()
    except AssertionError as e:
        why.append(f"cache invariants: {e}")
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
def logits_through_cache(eng, conf: dict, prompts: list, max_new: int
                         ) -> tuple:
    """Serve `prompts` ONE AFTER ANOTHER through a session (so a later
    prompt finds an earlier one's prefix in the cache), greedy, and
    compare the engine's top-k logits at every generated position with
    the reference's full forward pass over prompt + generated tokens.
    -> (one dict a prompt: hit tokens, prefill chunks, the worst
    absolute logit difference, the worst gap, the logits' deviation;
    the session's stats_dict())."""
    reference = reference_logits(conf)
    out = []
    session = eng.start_session()
    for prompt in prompts:
        req = session.submit(prompt, max_new)
        tops, hit, chunks = [], None, 0
        while session.has_work():
            ev = session.step()
            if ev is None:
                break
            for ch in (ev.plan.chunks if ev.plan else ()):
                if hit is None:
                    hit = int(ch.start)
                chunks += not ch.is_decode
            if ev.dispatched and ev.emitted:
                tops.append(emitted_logits(ev)[1:])
        n_p, n_g = len(prompt), len(req.out_tokens)
        ref = reference(eng.params, list(prompt) + list(req.out_tokens),
                        np.arange(n_p - 1, n_p - 1 + n_g))
        err = logit_errors(np.concatenate([v for v, _ in tops]),
                           np.concatenate([i for _, i in tops]), ref)
        gap = ref.max(axis=1) - ref[np.arange(n_g),
                                    np.asarray(req.out_tokens)]
        out.append({"prompt": n_p, "new": n_g, "hit_tokens": hit,
                    "prefill_chunks": chunks,
                    "logit_abs_err": float(np.abs(err).max()),
                    "logit_rms_err": rms([err]), "errors": err,
                    "worst_gap": float(gap.max()),
                    "argmax_agree": int((gap == 0).sum()),
                    "logit_std": float(ref.std())})
    stats = session.stats_dict()
    session.close()
    return out, stats
