"""The Phi-4-mini-flash serving cell from start to verdict.

What lib/olmoe_cell.py does for `olmoe-1b-7b-1chip`, for the
configuration `phi-4-mini-flash-1chip`: build the engine from the
configuration (`build_phi4flash_lm` + `ServeEngine`), make the traffic
from the seed, run ramp + window + drain through lib/serving.py, then
decide `correct` against lib/reference_phi4flash.py. The loop that
keeps the emitted tokens' logits, and the arithmetic on them, are
lib/olmoe_cell.py's (PERF.md section 7: fold the cells behind an
`architecture` key of the configuration).

Also here, for the CPU tests and the builder's chip check
(check_phi4flash_logits.py): the engine's LOGITS through pages, rings
and state slots against the reference's full forward pass at the same
positions.
"""

from __future__ import annotations

import functools
import time

import numpy as np

from . import checks, reference_phi4flash, serving, system, traffic_gen
from .olmoe_cell import Loop, emitted_logits, logit_errors, rms
from .window import Window

SEQ_BUCKETS = (512, 1024, 2048, 4096)


def build_engine(conf: dict, seed: int, interpret: bool = False,
                 warm: bool = True):
    """The serve engine over freshly initialised weights, its one mixed
    program warmed. -> (engine, seconds spent in warmup())."""
    from flexflow_tpu.config import CompMode, FFConfig
    from flexflow_tpu.models.phi4flash import build_phi4flash_lm
    from flexflow_tpu.serve import ServeEngine
    cfg = FFConfig(batch_size=1, seed=system.weight_seed(seed),
                   search_budget=0, **conf["system"])
    lm = build_phi4flash_lm(
        cfg, vocab_size=conf["vocab_size"],
        max_seq_len=conf["max_position_embeddings"],
        hidden=conf["hidden_size"], num_heads=conf["num_attention_heads"],
        num_kv_heads=conf["num_key_value_heads"],
        num_layers=conf["num_hidden_layers"],
        ff_dim=conf["intermediate_size"], window=conf["sliding_window"],
        ln_eps=float(conf["layer_norm_eps"]))
    lm.compile(comp_mode=CompMode.INFERENCE)
    eng = ServeEngine(lm, interpret=interpret)
    t0 = time.perf_counter()
    if warm:
        eng.warmup()
    return eng, time.perf_counter() - t0


def reference_logits(conf: dict):
    """-> f(params, seq, rows): the reference's logits (len(rows), V)
    of the token list `seq` at positions `rows`, the sequence padded to
    one of a few lengths (a few compiles)."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(functools.partial(
        reference_phi4flash.logits_at,
        num_layers=conf["num_hidden_layers"],
        window=conf["sliding_window"],
        ln_eps=float(conf["layer_norm_eps"])))
    positions = conf["max_position_embeddings"]

    def logits(params, seq, rows):
        n = next((b for b in SEQ_BUCKETS if len(seq) <= b <= positions),
                 positions)
        toks = np.zeros((1, n), np.int32)
        toks[0, :len(seq)] = seq
        return np.asarray(fn(params, jnp.asarray(toks), jnp.asarray(
            np.asarray(rows, np.int32))))

    return logits


def compare(reference, params, prompt, tokens, topv, topi,
            rows_padded: int = 0) -> dict:
    """One request: the reference's logits at its generated positions
    against the engine's top-k logits there, and the gap between the
    reference's best logit and its logit of the engine's token."""
    n_p, n_g = len(prompt), len(tokens)
    rows = np.zeros((max(rows_padded, n_g),), np.int32)
    rows[:n_g] = np.arange(n_p - 1, n_p - 1 + n_g)
    logits = reference(params, list(prompt) + list(tokens), rows)[:n_g]
    gap = logits.max(axis=1) - logits[np.arange(n_g), np.asarray(tokens)]
    err = logit_errors(topv[:n_g], topi[:n_g], logits)
    return {"prompt": n_p, "new": n_g, "worst_gap": float(gap.max()),
            "argmax_agree": int((gap == 0).sum()),
            "logit_abs_err": float(np.abs(err).max()),
            "logit_rms_err": rms([err]), "errors": err,
            "logit_std": float(logits.std())}


def check_serving(params, conf: dict, picks: list, max_new: int,
                  top_logits) -> dict:
    """lib/olmoe_cell.check_serving against this model's reference."""
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


def verdict(found: dict, chk: dict) -> list:
    """Why the logits are not correct under the configuration's two
    limits (empty: they are)."""
    why = []
    if found["worst_gap"] is None:
        return ["no completed request to compare"]
    if not found["worst_gap"] <= chk["logit_margin"]:
        why.append(
            f"the engine chose a token {found['worst_gap']:.4g} below "
            f"the reference's best (> {chk['logit_margin']})")
    if not found["logit_rms_err"] <= chk["logit_rms"]:
        why.append(
            f"the engine's logits differ from the reference's by "
            f"{found['logit_rms_err']:.4g} in the root mean square "
            f"(> {chk['logit_rms']})")
    return why


def run(ctx) -> dict:
    conf, t = ctx.conf, ctx.traffic
    eng, warmup_s = build_engine(conf, ctx.seed, ctx.rehearse)
    c = eng.cache_cfg
    ctx.say("engine", {
        "arch": eng.arch.kind, "lanes": eng.mixed_width,
        "max_seqs": c.max_seqs, "pages": c.num_pages,
        "ring_pages": c.ring_pages, "attn_impl": eng.attn_impl,
        "layers": eng.num_layers, "kinds": "".join(
            k[0] for k in eng.arch.kinds),
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
                   "cache_bytes_constant_per_seq"])

    # ---- correct: outside the window, its seconds on a line of its own
    t0 = time.perf_counter()
    picks = checks.pick_requests(loop.check_records(), eng.prefill_budget,
                                 ctx.seed, int(conf["check"]["requests"]))
    found = check_serving(eng.params, conf, picks, int(t["output"]["max"]),
                          loop.top_logits)
    why = verdict(found, conf["check"])
    if stats["nonfinite_logit_steps"]:
        why.append(f"{stats['nonfinite_logit_steps']} steps with "
                   f"non-finite logits")
    if eng.attn_impl != system.expected_attn_impl(ctx.rehearse):
        why.append(f"attention ran as {eng.attn_impl!r}")
    try:
        eng.cache.check_invariants(eng.pool)
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
def logits_through_cache(eng, conf: dict, groups: list, max_new: int,
                         on_step=None) -> tuple:
    """Serve `groups` one after another through ONE session, the
    prompts of a group TOGETHER (their chunks share steps, beside each
    other's decode lanes), greedy, and compare the engine's top-k
    logits at every generated position with the reference's full
    forward pass over prompt + generated tokens. `on_step(session,
    event)` runs after every step. -> (one dict a prompt: prefill
    chunks, the largest and the root mean square logit difference, the
    worst gap; the session's stats_dict())."""
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
            row.update(prefill_chunks=chunks[req.rid], together=len(reqs),
                       preemptions=int(getattr(req, "preemptions", 0)))
            out.append(row)
    stats = session.stats_dict()
    session.close()
    return out, stats
