#!/usr/bin/env python3
"""The Phi-4-mini-flash engine against its reference on LOGITS, outside
any window.

    python3 benchmark/check_phi4flash_logits.py --seed <n> [--variants base,fp8_pages,bf16_state]

At the configuration's published widths on the chip (`--rehearse-cpu`:
its rehearsal size, kernels interpreted): seeded prompts of 16 to 4000
tokens — one prefilled whole, one in two chunks, one in eight, two
TOGETHER (their chunks share steps with each other and with decode
lanes) — are prefilled, then decoded `--new` (64) tokens through pages,
rings and state slots, and the engine's top-k logits at every generated
position are compared with lib/reference_phi4flash.py's full forward
pass. Printed per prompt: the root mean square and the largest of the
logit differences (what `logit_rms` limits) and the worst gap (the
reference's best logit minus its logit of the token the engine chose,
what `logit_margin` limits).

`--variants` serves the SAME weights again in a precision below the
configuration's, each of which ought to fail its limits: `fp8_pages`
(kv_dtype float8_e4m3 for the paged layer and the rings) and
`bf16_state` (the scan state slab, and with it the carried state, in
bf16). The last line is one JSON object: a reading per variant.
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


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--variants", default="base")
    ap.add_argument("--new", type=int, default=64)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    from run import load_json, merge
    conf = load_json(HERE, "configs", "phi-4-mini-flash-1chip.json")
    if args.rehearse_cpu:
        conf = merge(conf, conf["rehearsal"])

    import jax
    import jax.numpy as jnp
    import numpy as np
    from flexflow_tpu.serve import ServeEngine
    from flexflow_tpu.utils.cache_dirs import arm_compile_cache
    from lib import olmoe_cell, phi4flash_cell
    if (jax.devices()[0].platform == "tpu") == args.rehearse_cpu:
        raise SystemExit("check_phi4flash_logits: a TPU, or --rehearse-cpu")
    arm_compile_cache()
    base, _ = phi4flash_cell.build_engine(conf, args.seed,
                                          args.rehearse_cpu, warm=False)
    rng = np.random.default_rng([args.seed, 5])
    scale = 16 if args.rehearse_cpu else 1
    budget = base.prefill_budget

    def toks(n):
        return rng.integers(1, conf["vocab_size"], n).tolist()

    groups = [[toks(16)], [toks(300 // scale)],
              [toks(budget + 190 // scale)],
              [toks(conf["max_position_embeddings"] // 2 - 96)],
              [toks(700 // scale), toks(1300 // scale)]]

    def variant(name):
        """The engine of a variant over the SAME model."""
        if name == "base":
            return base
        cfg = copy.copy(base.config)
        if name == "fp8_pages":
            cfg.kv_dtype = "float8_e4m3"
        eng = ServeEngine(base.model, interpret=args.rehearse_cpu,
                          config=cfg)
        if name == "bf16_state":
            pool = eng._device_pool()
            eng.pool = dataclasses.replace(
                pool, state=pool.state.astype(jnp.bfloat16))
        elif name != "fp8_pages":
            raise SystemExit(f"no variant {name!r}")
        return eng

    out = {"seed": args.seed, "device": jax.devices()[0].device_kind,
           "layers": conf["num_hidden_layers"], "new": args.new,
           "logit_margin": conf["check"]["logit_margin"],
           "logit_rms": conf["check"]["logit_rms"], "variants": {}}
    for name in args.variants.split(","):
        eng = variant(name)
        eng.warmup()
        rows, stats = phi4flash_cell.logits_through_cache(
            eng, conf, groups, args.new)
        errors = [r.pop("errors") for r in rows]
        for r in rows:
            print(f"# {name} prompt: " + json.dumps(r), flush=True)
        found = {
            "kv_dtype": eng.kv_dtype,
            "state_dtype": str(eng.pool.state.dtype),
            "prompts": len(rows),
            "positions": sum(r["new"] for r in rows),
            "argmax_agree": sum(r["argmax_agree"] for r in rows),
            "logit_rms_err": olmoe_cell.rms(errors),
            "logit_max_abs_err": max(r["logit_abs_err"] for r in rows),
            "worst_gap": max(r["worst_gap"] for r in rows),
            "logit_std": float(np.mean([r["logit_std"] for r in rows])),
            "chunked": max(r["prefill_chunks"] for r in rows),
            "compiles": eng.compile_counts()["mixed"],
            "nonfinite_logit_steps": stats["nonfinite_logit_steps"]}
        found["why_incorrect"] = phi4flash_cell.verdict(found, conf["check"])
        out["variants"][name] = found
        eng.pool = None             # the next variant's pools need the room
        eng.close()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
