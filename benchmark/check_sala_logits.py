#!/usr/bin/env python3
"""The MiniCPM-SALA engine against its reference on LOGITS, outside any
window.

    python3 benchmark/check_sala_logits.py --seed <n> [--variants base,fp8_pages,bf16_state,wrong_selected_block] [--longest 49000]

At the configuration's published widths on the chip (`--rehearse-cpu`:
its rehearsal size, kernels interpreted): seeded prompts of 16 to
`--longest` tokens — one prefilled whole, one in two chunks, two
TOGETHER (so the longer one's chunks do not start at multiples of the
prefill budget, and it crosses `dense_len` INSIDE a chunk), two long
documents — are prefilled, then decoded `--new` (64) tokens through
pages, compressed keys and state slots, and the engine's top-k logits
at every generated position are compared with lib/reference_sala.py's
full forward pass. Printed per prompt: the root mean square and the
largest of the logit differences (what `logit_rms` limits) and the
worst gap (the reference's best logit minus its logit of the token the
engine chose, what `logit_margin` limits); per variant both numbers
over all the prompts and, under `past_dense_len`, over the prompts of
the cell's lengths alone, each with lib/sala_cell's verdict.

`--variants` serves the SAME weights again, each of which ought to fail
a limit: `fp8_pages` (kv_dtype float8_e4m3 for the sparse layers'
pages: the precision below the stated bf16), `bf16_state` (the matrix
state slab, and with it the carried state, in bf16) and
`wrong_selected_block` (a planted fault: once a sequence's prompt is
in, the four pages of its block 0 — the `init_blocks` block EVERY query
past dense_len selects — hold block 1's keys and values in every sparse
layer: the selected-block attention reads a wrong block in its place).
Last, on the reference alone: the selector with f32 operands against
the configuration's bf16 ones (what the flipped near-ties cost). The
last line is one JSON object: a reading per variant.
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


def plant_wrong_selected_block(eng):
    """-> on_step(session, event): once a request's prompt is in, its
    block 0 holds block 1's keys and values in the sparse layers."""
    planted = set()
    c = eng.cache_cfg
    bp = eng.arch.sparse.block_size // c.page_size

    def on_step(session, ev):
        for ch in (ev.plan.chunks if ev.plan else ()):
            req = ch.req
            if req.rid in planted or not ch.is_decode \
                    or len(req.prompt) < 2 * bp * c.page_size:
                continue
            planted.add(req.rid)
            row = eng.cache.page_tables[req.slot]
            dst, src = row[:bp], row[bp:2 * bp]
            full = eng.pool.full
            eng.pool = dataclasses.replace(
                eng.pool, full=dataclasses.replace(
                    full, k=full.k.at[:, dst].set(full.k[:, src]),
                    v=full.v.at[:, dst].set(full.v[:, src])))

    return on_step


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--variants", default="base")
    ap.add_argument("--new", type=int, default=64)
    ap.add_argument("--longest", type=int, default=49000)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    from run import load_json, merge
    conf = load_json(HERE, "configs", "minicpm-sala-1chip-l16.json")
    if args.rehearse_cpu:
        conf = merge(conf, conf["rehearsal"])

    import jax
    import jax.numpy as jnp
    import numpy as np
    from flexflow_tpu.serve import ServeEngine
    from flexflow_tpu.utils.cache_dirs import arm_compile_cache
    from lib import olmoe_cell, sala_cell
    if (jax.devices()[0].platform == "tpu") == args.rehearse_cpu:
        raise SystemExit("check_sala_logits: a TPU, or --rehearse-cpu")
    arm_compile_cache()
    base, _ = sala_cell.build_engine(conf, args.seed, args.rehearse_cpu,
                                     warm=False)
    rng = np.random.default_rng([args.seed, 5])
    scale = 128 if args.rehearse_cpu else 1
    budget = base.prefill_budget
    dense_len = conf["sparse_config"]["dense_len"]

    def toks(n):
        return rng.integers(1, conf["vocab_size"], max(4, n)).tolist()

    groups = [[toks(16)], [toks(300 // scale)],
              [toks(budget + 190 // scale)],
              # together: the second's chunks start off the budget's
              # multiples, and one of them holds position dense_len
              [toks(budget + 188 // scale), toks(dense_len + 808 // scale)],
              [toks(20000 // scale)], [toks(args.longest // scale)]]

    def variant(name):
        """(the engine, its on_step) of a variant over the SAME model."""
        if name == "base":
            return base, None
        if name == "wrong_selected_block":
            return base, plant_wrong_selected_block(base)
        cfg = copy.copy(base.config)
        if name == "fp8_pages":
            cfg.kv_dtype = "float8_e4m3"
        elif name != "bf16_state":
            raise SystemExit(f"no variant {name!r}")
        eng = ServeEngine(base.model, interpret=args.rehearse_cpu,
                          config=cfg)
        if name == "bf16_state":
            pool = eng._device_pool()
            eng.pool = dataclasses.replace(
                pool, state=pool.state.astype(jnp.bfloat16))
        return eng, None

    out = {"seed": args.seed, "device": jax.devices()[0].device_kind,
           "layers": conf["num_hidden_layers"], "new": args.new,
           "logit_margin": conf["check"]["logit_margin"],
           "logit_rms": conf["check"]["logit_rms"], "variants": {}}
    for name in args.variants.split(","):
        eng, on_step = variant(name)
        eng.warmup()
        rows, stats = sala_cell.logits_through_cache(
            eng, conf, groups, args.new, on_step)
        errors = [r.pop("errors") for r in rows]
        for r in rows:
            print(f"# {name} prompt: " + json.dumps(r), flush=True)

        def reading(keep):
            """The cell's two numbers over the prompts `keep` picks."""
            sub = [(r, e) for r, e in zip(rows, errors) if keep(r)]
            return {"prompts": len(sub),
                    "positions": sum(r["new"] for r, _ in sub),
                    "argmax_agree": sum(r["argmax_agree"] for r, _ in sub),
                    "logit_rms_err": olmoe_cell.rms([e for _, e in sub]),
                    "logit_max_abs_err": max(r["logit_abs_err"]
                                             for r, _ in sub),
                    "worst_gap": max(r["worst_gap"] for r, _ in sub)}

        found = {
            "kv_dtype": eng.kv_dtype,
            "state_dtype": str(eng.pool.state.dtype),
            **reading(lambda r: True),
            "by_prompt": [[r["prompt"], r["logit_rms_err"]] for r in rows],
            "logit_std": float(np.mean([r["logit_std"] for r in rows])),
            "chunked": max(r["prefill_chunks"] for r in rows),
            "compiles": eng.compile_counts()["mixed"],
            "nonfinite_logit_steps": stats["nonfinite_logit_steps"]}
        found["why_incorrect"] = sala_cell.verdict(found, conf["check"])
        # the prompts of the CELL's lengths (every request of
        # sala-longdoc crosses dense_len), judged as the cell judges
        found["past_dense_len"] = reading(lambda r: r["prompt"] > dense_len)
        found["past_dense_len"]["why_incorrect"] = sala_cell.verdict(
            found["past_dense_len"], conf["check"])
        out["variants"][name] = found
        eng.pool = None             # the next variant's pools need the room
        if eng is not base:
            eng.close()

    # the selector's operands in f32 against the configuration's bf16,
    # on the reference alone, at positions past dense_len
    seq = toks((dense_len + 3800) // scale if not args.rehearse_cpu
               else 3 * dense_len)
    at = np.arange(dense_len, len(seq), dtype=np.int32)
    ref = sala_cell.reference_logits(
        conf, selector_dtype=None)(base.params, seq, at)
    low = sala_cell.reference_logits(conf)(base.params, seq, at)
    rows = np.arange(len(at))
    gap = ref.max(axis=1) - ref[rows, low.argmax(axis=1)]
    topi = np.argsort(-low, axis=1)[:, :base.topk_cap]
    err = olmoe_cell.logit_errors(np.take_along_axis(low, topi, axis=1),
                                  topi, ref)
    out["f32_selector_reference"] = {
        "worst_gap": float(gap.max()), "logit_rms_err": olmoe_cell.rms([err]),
        "logit_max_abs_err": float(np.abs(low - ref).max()),
        "argmax_differs": int((gap > 0).sum()), "positions": int(len(at))}
    base.close()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
