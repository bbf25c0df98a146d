#!/usr/bin/env python3
"""The Command A+ engine against its reference on LOGITS, outside any
window.

    python3 benchmark/check_cmdaplus_logits.py --seed <n> [--variants base,fp8_pages]

At the configuration's published widths on the chip (`--rehearse-cpu`:
its rehearsal size, kernels interpreted): seeded prompts of 16 to
16,000 tokens — one prefilled whole, one in two chunks, one past the
window in a dozen chunks, one of 16,000 tokens in 32, two TOGETHER
(their chunks share steps with each other and with decode lanes) — are
prefilled, then decoded `--new` (64) tokens greedily through pages and
rings, and the engine's top-k logits at every generated position are
compared with lib/reference_cmdaplus.py's full forward pass. Printed
per prompt: the root mean square and the largest of the logit
differences (what `logit_rms` limits, over every position of every
prompt) and the worst gap (the reference's best logit minus its logit
of the token the engine chose, what `logit_margin` limits).

`--variants` serves the SAME weights again with something that ought
to fail the configuration's limits: `fp8_pages`, the precision below
(kv_dtype float8_e4m3 for the full layer's pages and the rings), and
`wrong_ring_page`, a planted fault (`plant_wrong_ring_page`: once a
prompt is in the cache, ONE page of its slot's ring holds the next
page's keys and values in every window layer). Also printed, on the
reference alone (one 4,000-token sequence, every position a decision):
what the router with f32 operands reads against the configuration's,
whose operands are bf16. The last line is one JSON object: a reading
per variant.
"""

import argparse
import copy
import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))


def plant_wrong_ring_page(eng):
    """-> on_step(session, event) that plants ONE fault a request: at
    the step that emits its first token (its prompt is in the cache),
    the page of its slot's ring that holds the prompt's middle token is
    overwritten, in every window layer, with the ring's next page — 16
    keys and values of the window are another 16's, as a ring index
    that is off by one page would read them."""
    import jax
    c = eng.cache_cfg
    copy_page = jax.jit(lambda a, dst, src: a.at[:, dst].set(a[:, src]),
                        donate_argnums=0)
    planted = set()

    def on_step(session, ev):
        for req, _ in (ev.emitted if ev is not None else ()):
            if req.rid in planted or req.slot < 0:
                continue
            planted.add(req.rid)
            first = 1 + req.slot * c.ring_pages     # kv_cache.ring_tables
            page = len(req.prompt) // 2 // c.page_size
            dst = first + page % c.ring_pages
            src = first + (page + 1) % c.ring_pages
            w = eng.pool.window
            eng.pool = dataclasses.replace(eng.pool, window=dataclasses.replace(
                w, k=copy_page(w.k, dst, src), v=copy_page(w.v, dst, src)))

    return on_step


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--variants", default="base")
    ap.add_argument("--new", type=int, default=64)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    from run import load_json, merge
    conf = load_json(HERE, "configs", "command-a-plus-1chip-ep8.json")
    if args.rehearse_cpu:
        conf = merge(conf, conf["rehearsal"])

    import jax
    import numpy as np
    from flexflow_tpu.serve import ServeEngine
    from flexflow_tpu.utils.cache_dirs import arm_compile_cache
    from lib import cmdaplus_cell, olmoe_cell
    if (jax.devices()[0].platform == "tpu") == args.rehearse_cpu:
        raise SystemExit("check_cmdaplus_logits: a TPU, or --rehearse-cpu")
    arm_compile_cache()
    base, _ = cmdaplus_cell.build_engine(conf, args.seed,
                                         args.rehearse_cpu, warm=False)
    rng = np.random.default_rng([args.seed, 5])
    scale = 40 if args.rehearse_cpu else 1
    budget, window = base.prefill_budget, conf["sliding_window"]

    def toks(n):
        return rng.integers(1, conf["vocab_size"], n).tolist()

    groups = [[toks(16)], [toks(300 // scale)],
              [toks(budget + 190 // scale)],
              [toks(window + 1500 // scale)], [toks(16000 // scale)],
              [toks(700 // scale), toks(window + 400 // scale)]]

    def variant(name):
        """(the engine, its on_step) of a variant over the SAME model."""
        if name == "base":
            return base, None
        if name == "wrong_ring_page":
            return base, plant_wrong_ring_page(base)
        if name != "fp8_pages":
            raise SystemExit(f"no variant {name!r}")
        cfg = copy.copy(base.config)
        cfg.kv_dtype = "float8_e4m3"
        return ServeEngine(base.model, interpret=args.rehearse_cpu,
                           config=cfg), None

    out = {"seed": args.seed, "device": jax.devices()[0].device_kind,
           "layers": conf["num_hidden_layers"], "new": args.new,
           "logit_margin": conf["check"]["logit_margin"],
           "logit_rms": conf["check"]["logit_rms"], "variants": {}}
    for name in args.variants.split(","):
        eng, on_step = variant(name)
        eng.warmup()
        rows, stats = cmdaplus_cell.logits_through_cache(
            eng, conf, groups, args.new, on_step)
        errors = [r.pop("errors") for r in rows]
        for r in rows:
            print(f"# {name} prompt: " + json.dumps(r), flush=True)
        ex = stats["experts"]
        found = {
            "kv_dtype": eng.kv_dtype, "prompts": len(rows),
            "positions": sum(r["new"] for r in rows),
            "argmax_agree": sum(r["argmax_agree"] for r in rows),
            "logit_rms_err": olmoe_cell.rms(errors),
            "logit_max_abs_err": max(r["logit_abs_err"] for r in rows),
            "worst_gap": max(r["worst_gap"] for r in rows),
            "logit_std": float(np.mean([r["logit_std"] for r in rows])),
            "chunked": max(r["prefill_chunks"] for r in rows),
            "compiles": eng.compile_counts()["mixed"],
            "expert_dropped": ex["dropped"],
            "expert_held_share": float(ex["counts"].sum() / ex["slots"]),
            "nonfinite_logit_steps": stats["nonfinite_logit_steps"]}
        found["why_incorrect"] = cmdaplus_cell.verdict(found, conf["check"])
        out["variants"][name] = found
        eng.pool = None             # the next variant's pools need the room
        if eng is not base:
            eng.close()

    # the router's operands in f32 against the configuration's bf16, on
    # the reference alone
    seq = toks(4000 // scale)
    at = np.arange(len(seq), dtype=np.int32)
    ref = cmdaplus_cell.reference_logits(
        conf, router_dtype=None)(base.params, seq, at)
    low = cmdaplus_cell.reference_logits(conf)(base.params, seq, at)
    gap = ref.max(axis=1) - ref[at, low.argmax(axis=1)]
    topi = np.argsort(-low, axis=1)[:, :base.topk_cap]
    err = olmoe_cell.logit_errors(np.take_along_axis(low, topi, axis=1),
                                  topi, ref)
    out["f32_router_reference"] = {
        "worst_gap": float(gap.max()), "logit_rms_err": olmoe_cell.rms([err]),
        "logit_max_abs_err": float(np.abs(low - ref).max()),
        "argmax_differs": int((gap > 0).sum()), "positions": int(len(at))}
    base.close()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
