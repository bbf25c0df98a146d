"""Training, steps back to back: a fresh seeded batch every step, the
loss fetched each step (which closes the step's time)."""

from __future__ import annotations

import time

import numpy as np

from lib import checks, flops, system, traffic_gen
from lib.chip_state import step_ms_thirds
from lib.window import Window


def run(ctx):
    import jax
    conf = ctx.conf
    tr = conf["train"]
    devices = jax.devices()[:ctx.chips]
    lm, info = system.build_trainer(conf, ctx.seed, devices, ctx.say)
    batch_n, seq = int(tr["global_batch"]), conf["max_position_embeddings"]
    vocab = conf["vocab_size"]

    def batch(i):
        return traffic_gen.token_batch(ctx.seed, i, batch_n, seq, vocab)

    # ---- correct, part 1: the first step against the reference (this
    # step is also the one that compiles; all of it is set-up)
    found = checks.check_first_step(lm, conf, batch(0), ctx.seed)
    why = checks.verdict_first_step(found, conf["check"])
    ctx.say("check", {**found, "why_incorrect": why})
    for i in range(1, 1 + int(ctx.traffic.get("warm_steps", 2))):
        float(lm.train_batch(batch(i))["loss"])

    win = Window(ctx.spans, lm.compile_counts, ctx.trace_dir,
                 float(ctx.traffic.get("trace_s", 3.0)))
    losses, step_s, fetched_at = [], [], []
    nxt = batch(100)            # made ahead: the host's part of a step
    ctx.chip.take("before_ramp")
    w0 = time.perf_counter()
    w1 = w0 + ctx.seconds
    i = 100
    while True:
        now = time.perf_counter()
        win.tick(now, w0, w1)
        if now >= w1:
            break
        t0 = now
        with ctx.spans.span("train_batch"):
            out = lm.train_batch(nxt)
        with ctx.spans.span("next_batch"):
            i += 1
            nxt = batch(i)
        with ctx.spans.span("loss_fetch"):
            losses.append(float(out["loss"]))
        fetched_at.append(time.perf_counter())
        step_s.append(fetched_at[-1] - t0)
    ctx.chip.take("after_drain")    # before the profiler stops
    trace = win.finish(ctx.chips)
    # a step counts if its loss fetch completed inside the window
    done = sum(1 for t in fetched_at if t <= w1)
    bad = int(np.sum(~np.isfinite(losses)))
    if bad:
        why.append(f"{bad} steps with a non-finite loss")
    compiles = win.compiles_in_window()
    if compiles:
        why.append(f"{compiles} compiles in the window")
    ctx.say("losses", {"first_step": found["loss_sys"],
                       "window_first": losses[0] if losses else None,
                       "window_last": losses[-1] if losses else None,
                       "steps": len(losses)})
    tokens_per_step = batch_n * seq
    num = {"seconds": ctx.seconds, "setup_s": w0 - ctx.t_process_start,
           "steps_done": done, "tokens_per_step": tokens_per_step,
           "train_tokens": done * tokens_per_step, "step_s": step_s,
           "step_ms_thirds": step_ms_thirds(
               [t - s for t, s in zip(fetched_at, step_s)], step_s, w0, w1),
           "compiles_in_window": compiles, "search_s": info["search_s"],
           "flops_per_token": flops.lm_train_flops_per_token(conf, seq),
           "chips": ctx.chips}
    return {"numbers": num, "trace": trace, "correct": not why,
            "attempted": len(losses), "failed": bad}
