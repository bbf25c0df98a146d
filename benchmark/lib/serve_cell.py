"""A serving cell from start to verdict: build the engine from the
configuration, make the traffic from the seed, run ramp + window +
drain through lib/serving.py, then check the outputs against the
reference. The two serve drivers differ only in who feeds the loop."""

from __future__ import annotations

import time

from . import checks, serving, system, traffic_gen
from .window import Window


def run(ctx, open_loop: bool) -> dict:
    conf, t = ctx.conf, ctx.traffic
    eng, warmup_s = system.build_engine(conf, ctx.seed, ctx.rehearse)
    ctx.say("engine", {
        "lanes": eng.mixed_width, "max_seqs": eng.cache_cfg.max_seqs,
        "pages": eng.cache_cfg.num_pages, "attn_impl": eng.attn_impl,
        "layers": eng.num_layers, "warmup_s": warmup_s,
        "spec_tokens": eng.spec_tokens})
    vocab = conf["vocab_size"]
    t0 = time.perf_counter()
    if open_loop:
        reqs = traffic_gen.make_requests(t, ctx.seed, vocab,
                                         int(t["pool_requests"]))
    else:
        reqs = traffic_gen.make_document_asks(t, ctx.seed, vocab,
                                              int(t["documents"]))
    ctx.say("traffic", {"requests_made": len(reqs),
                        "make_s": time.perf_counter() - t0,
                        "prompt_tokens_mean":
                            sum(len(r.prompt) for r in reqs) / len(reqs),
                        "max_new_mean":
                            sum(r.max_new for r in reqs) / len(reqs)})
    loop = serving.ServeLoop(eng, ctx.spans)
    win = Window(ctx.spans, eng.compile_counts, ctx.trace_dir,
                 float(t.get("trace_s", 5.0)))
    ramp, drain = float(t["ramp_s"]), float(t["drain_s"])
    ctx.chip.take("before_ramp")
    if open_loop:
        w = serving.run_open_loop(loop, reqs, ramp, ctx.seconds, drain,
                                  win.tick)
    else:
        w = serving.run_closed_loop(loop, reqs, int(t["clients"]), ramp,
                                    ctx.seconds, drain, win.tick)
    ctx.chip.take("after_drain")    # before the profiler stops
    trace = win.finish(ctx.chips)
    stats = loop.close()
    num = serving.window_numbers(loop, w, open_loop)
    num.update(setup_s=win.t_open - ctx.t_process_start,
               compiles_in_window=win.compiles_in_window(),
               drain_s=w["t_end"] - w["w1"],
               nonfinite_logit_steps=stats["nonfinite_logit_steps"],
               preemptions=stats["preemptions"],
               rejected=stats["rejected"])

    # ---- correct: outside the window, its seconds on a line of its own
    t0 = time.perf_counter()
    why = []
    chk = conf["check"]
    picks = checks.pick_requests(loop.check_records(), eng.prefill_budget,
                                 ctx.seed, int(chk["requests"]))
    found = checks.check_serving(eng.params, eng.num_layers,
                                 conf["max_position_embeddings"], picks,
                                 int(t["output"]["max"]))
    if found["worst_gap"] is None:
        why.append("no completed request to compare")
    elif not found["worst_gap"] <= chk["logit_margin"]:
        why.append(f"the engine chose a token {found['worst_gap']:.4g} "
                   f"below the reference's best (> {chk['logit_margin']})")
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
