#!/usr/bin/env python3
"""The OLMoE engine against its reference on LOGITS, outside any window.

    python3 benchmark/check_olmoe_logits.py --seed <n> [--kv-dtype <fmt>]

At the configuration's published widths on the chip (`--rehearse-cpu`:
its rehearsal size, kernels interpreted): seeded prompts of 16 to 1024
tokens — one prefilled in more than one chunk, one that finds a
64-token prefix in the cache — are prefilled, then decoded 32 tokens
through the paged cache, and the engine's top-k logits at every
generated position are compared with lib/reference_olmoe.py's full
forward pass. Printed: per prompt the root mean square and the largest
of the logit differences (what `logit_rms` limits) and the worst gap
(reference's best logit minus its logit of the token the engine chose,
what `logit_margin` limits); how often the router's 8th and 9th probabilities
lie within bf16's resolution of each other (where an expert may swap);
and the readings in a precision BELOW the configuration's, which
ought to fail its limits: the reference itself with every weight
matrix rounded to fp8 (e4m3), and with only its router computed in
bf16 (their own top-k logits against the f32 reference's).
`--kv-dtype` serves from pages of another format (float8_e4m3: the
precision below the configuration's bf16 pages).
The last line is one JSON object.
"""

import argparse
import functools
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kv-dtype", default=None)
    ap.add_argument("--new", type=int, default=32)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    from run import load_json, merge
    conf = load_json(HERE, "configs", "olmoe-1b-7b-1chip.json")
    if args.rehearse_cpu:
        conf = merge(conf, conf["rehearsal"])
    if args.kv_dtype:
        conf["system"]["kv_dtype"] = args.kv_dtype

    import jax
    import jax.numpy as jnp
    import numpy as np
    from flexflow_tpu.utils.cache_dirs import arm_compile_cache
    from lib import checks, olmoe_cell, reference_olmoe
    if (jax.devices()[0].platform == "tpu") == args.rehearse_cpu:
        raise SystemExit("check_olmoe_logits: a TPU, or --rehearse-cpu")
    arm_compile_cache()
    eng, _ = olmoe_cell.build_engine(conf, args.seed, args.rehearse_cpu)
    rng = np.random.default_rng([args.seed, 5])
    scale = 16 if args.rehearse_cpu else 1
    budget = eng.prefill_budget

    def toks(n):
        return rng.integers(1, conf["vocab_size"], n).tolist()

    prefix = toks(64 // (4 if args.rehearse_cpu else 1))
    prompts = [toks(16), toks(300 // scale), toks(budget + 190 // scale),
               toks(1024 // scale), prefix + toks(200 // scale),
               prefix + toks(150 // scale)]
    rows, stats = olmoe_cell.logits_through_cache(eng, conf, prompts,
                                                  args.new)
    errors = [r.pop("errors") for r in rows]
    for r in rows:
        print("# prompt: " + json.dumps(r), flush=True)

    # where an expert may swap, and the lower-precision reading, on the
    # longest sequence (prompt only: every position is a decision)
    kw = dict(num_layers=conf["num_hidden_layers"],
              **olmoe_cell.model_args(conf))
    longest = max(prompts, key=len)
    seq = np.zeros((1, checks._bucket(len(longest),
                                      conf["max_position_embeddings"])),
                   np.int32)
    seq[0, :len(longest)] = longest
    seq = jnp.asarray(seq[:, :len(longest)])
    near, total = jax.jit(functools.partial(
        reference_olmoe.near_ties, within=2.0 ** -8, **kw))(eng.params, seq)
    at = jnp.arange(len(longest), dtype=jnp.int32)
    ref = np.asarray(jax.jit(functools.partial(
        reference_olmoe.logits_at, **kw))(eng.params, seq, at))
    low = np.asarray(jax.jit(functools.partial(
        reference_olmoe.logits_at, router_dtype=jnp.bfloat16, **kw))(
            eng.params, seq, at))

    def fp8_weights(p, tokens, rows):
        # rounded where each matrix is used: no second copy of the model
        return reference_olmoe.logits_at(jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float8_e4m3fn).astype(a.dtype)
            if a.ndim >= 2 else a, p), tokens, rows, **kw)

    low8 = np.asarray(jax.jit(fp8_weights)(eng.params, seq, at))

    def reading(other):
        gap = ref.max(axis=1) - ref[np.arange(len(at)), other.argmax(axis=1)]
        topi = np.argsort(-other, axis=1)[:, :eng.topk_cap]
        err = olmoe_cell.logit_errors(
            np.take_along_axis(other, topi, axis=1), topi, ref)
        return {"worst_gap": float(gap.max()),
                "logit_rms_err": olmoe_cell.rms([err]),
                "logit_max_abs_err": float(np.max(np.abs(other - ref))),
                "argmax_differs": int((gap > 0).sum()),
                "positions": int(len(at))}
    out = {
        "seed": args.seed, "kv_dtype": conf["system"]["kv_dtype"],
        "device": jax.devices()[0].device_kind,
        "layers": conf["num_hidden_layers"], "prompts": len(rows),
        "positions": sum(r["new"] for r in rows),
        "argmax_agree": sum(r["argmax_agree"] for r in rows),
        "logit_rms_err": olmoe_cell.rms(errors),
        "logit_max_abs_err": max(r["logit_abs_err"] for r in rows),
        "worst_gap": max(r["worst_gap"] for r in rows),
        "logit_std": float(np.mean([r["logit_std"] for r in rows])),
        "chunked": max(r["prefill_chunks"] for r in rows),
        "hit_tokens": max(r["hit_tokens"] for r in rows),
        "expert_dropped": stats["experts"]["dropped"],
        "router_near_ties": int(near),
        "router_decisions": int(total),
        "fp8_weights_reference": reading(low8),
        "bf16_router_reference": reading(low),
        "logit_margin": conf["check"]["logit_margin"],
        "logit_rms": conf["check"]["logit_rms"],
    }
    eng.close()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
