#!/usr/bin/env python3
"""The Qwen3-Next engine against its reference on LOGITS, outside any
window.

    python3 benchmark/check_qwen3next_logits.py --seed <n> [--variants base,fp8_pages,wrong_page,no_correction,plain_norm_scale,no_output_gate] [--longest 24000]

At the configuration's published widths on the chip (`--rehearse-cpu`:
its rehearsal size, kernels interpreted): seeded prompts of 16 to
`--longest` tokens — one prefilled whole, one in two chunks, two
TOGETHER (so the longer one's chunks do not start at multiples of the
prefill budget and its runs share blocks of lanes with the other's), a
mean prompt of the cell and a long one — are prefilled, then decoded
`--new` (64) tokens through pages, state slots and tails, and the
engine's top-k logits at every generated position are compared with
lib/reference_qwen3next.py's full forward pass. Printed per prompt: the
root mean square and the largest of the logit differences (what
`logit_rms` limits) and the worst gap (the reference's best logit minus
its logit of the token the engine chose, what `logit_margin` limits);
per variant both numbers over all the prompts, with the cell's verdict.

`--variants` serves the SAME weights again, each of which ought to fail
a limit:
  fp8_pages         kv_dtype float8_e4m3 for the full layers' pages: the
                    precision below the stated bf16;
  wrong_page        a planted fault: once a sequence's prompt is in, its
                    first page holds its second page's keys and values
                    in every full layer;
  no_correction     the delta rule without its correction term
                    (S += k (beta v)^T: plain gated linear attention),
                    in the step's lane and chunk forms alike;
  plain_norm_scale  `w` in place of `1 + w` in the zero-centred norms;
  no_output_gate    the full layers' output gate left out.
The last three are faults of the PROGRAM's mathematics: the engine is
built again from the same weights with the faulty function in the
program's place (the reference is never touched). The last line is one
JSON object: a reading per variant.
"""

import argparse
import contextlib
import copy
import dataclasses
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

CONFIG = "qwen3-next-80b-a3b-1chip-ep4-l8.json"


def plant_wrong_page(eng):
    """-> on_step(session, event): once a request's prompt is in, its
    first page holds its second page's keys and values in the full
    layers."""
    planted = set()
    ps = eng.cache_cfg.page_size

    def on_step(session, ev):
        for ch in (ev.plan.chunks if ev.plan else ()):
            req = ch.req
            if req.rid in planted or not ch.is_decode \
                    or len(req.prompt) < 2 * ps:
                continue
            planted.add(req.rid)
            dst, src = eng.cache.page_tables[req.slot][:2]
            full = eng.pool.full
            eng.pool = dataclasses.replace(
                eng.pool, full=dataclasses.replace(
                    full, k=full.k.at[:, dst].set(full.k[:, src]),
                    v=full.v.at[:, dst].set(full.v[:, src])))

    return on_step


@contextlib.contextmanager
def faulty_program(name):
    """The program with ONE piece of its mathematics at fault, while an
    engine is built and traced under it."""
    import jax.numpy as jnp
    from flexflow_tpu.ops import gated_attention as GA
    from flexflow_tpu.ops import gated_delta as GD
    from flexflow_tpu.ops.common import rms_norm
    from flexflow_tpu.serve.arch import Qwen3Next
    if name == "no_correction":
        def token(s, q, k, v, g, beta):
            s = s * jnp.exp(g)[:, None, None] \
                + k[:, :, None] * (beta[:, None] * v)[:, None, :]
            return s, jnp.sum(q[:, :, None] * s, axis=1)

        def chunk(s, q, k, v, g, beta):
            """Plain gated linear attention over a chunk: the WY form
            with T = I."""
            cum = jnp.cumsum(g, axis=0)
            i = jnp.arange(q.shape[0])
            low = (i[:, None] >= i[None, :])[None]
            diff = cum.T[:, :, None] - cum.T[:, None, :]
            decay = jnp.where(low, jnp.exp(jnp.where(low, diff, 0.0)), 0.0)
            vn = v * beta[:, :, None]
            hi = GD._HI
            qk = jnp.einsum("ihd,jhd->hij", q, k, precision=hi) * decay
            o = jnp.einsum("ihk,hkv->ihv", q * jnp.exp(cum)[:, :, None], s,
                           precision=hi) \
                + jnp.einsum("hij,jhv->ihv", qk, vn, precision=hi)
            s = jnp.exp(cum[-1])[:, None, None] * s + jnp.einsum(
                "jhk,jhv->hkv", k * jnp.exp(cum[-1][None] - cum)[:, :, None],
                vn, precision=hi)
            return s, o

        patches = [(GD, "_token", token), (GD, "_chunk", chunk)]
    elif name == "plain_norm_scale":
        patches = [(GA, "rms_norm0", rms_norm)]
    elif name == "no_output_gate":
        patches = [(Qwen3Next, "attn_gate",
                    staticmethod(lambda o, gate: o))]
    else:
        raise SystemExit(f"no variant {name!r}")
    saved = [(obj, attr, obj.__dict__[attr]) for obj, attr, _ in patches]
    for obj, attr, new in patches:
        setattr(obj, attr, new)
    try:
        yield
    finally:
        for obj, attr, old in saved:
            setattr(obj, attr, old)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--variants", default="base")
    ap.add_argument("--new", type=int, default=64)
    ap.add_argument("--longest", type=int, default=24000)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    from run import load_json, merge
    conf = load_json(HERE, "configs", CONFIG)
    if args.rehearse_cpu:
        conf = merge(conf, conf["rehearsal"])

    import jax
    import numpy as np
    from flexflow_tpu.serve import ServeEngine
    from flexflow_tpu.utils.cache_dirs import arm_compile_cache
    from lib import olmoe_cell, qwen3next_cell
    if (jax.devices()[0].platform == "tpu") == args.rehearse_cpu:
        raise SystemExit("check_qwen3next_logits: a TPU, or --rehearse-cpu")
    arm_compile_cache()
    base, _ = qwen3next_cell.build_engine(conf, args.seed,
                                          args.rehearse_cpu, warm=False)
    rng = np.random.default_rng([args.seed, 5])
    scale = 64 if args.rehearse_cpu else 1
    budget = base.prefill_budget

    def toks(n):
        return rng.integers(1, conf["vocab_size"], max(4, n)).tolist()

    groups = [[toks(16)], [toks(300 // scale)],
              [toks(budget + 190 // scale)],
              # together: the second's chunks start off the budget's
              # multiples, and their runs share blocks of lanes
              [toks(budget + 188 // scale), toks(3 * budget + 808 // scale)],
              [toks(4160 // scale)], [toks(args.longest // scale)]]

    def variant(name):
        """(the engine, its on_step) of a variant over the SAME model."""
        if name == "base":
            return base, None
        if name == "wrong_page":
            return base, plant_wrong_page(base)
        if name == "fp8_pages":
            cfg = copy.copy(base.config)
            cfg.kv_dtype = "float8_e4m3"
            return ServeEngine(base.model, interpret=args.rehearse_cpu,
                               config=cfg), None
        with faulty_program(name):
            eng = ServeEngine(base.model, interpret=args.rehearse_cpu)
            eng.warmup()            # traced while the fault is in place
        return eng, None

    out = {"seed": args.seed, "device": jax.devices()[0].device_kind,
           "layers": conf["num_hidden_layers"], "new": args.new,
           "logit_margin": conf["check"]["logit_margin"],
           "logit_rms": conf["check"]["logit_rms"], "variants": {}}
    for name in args.variants.split(","):
        base.pool = None            # one engine's pools at a time
        eng, on_step = variant(name)
        eng.warmup()
        rows, stats = qwen3next_cell.logits_through_cache(
            eng, conf, groups, args.new, on_step)
        errors = [r.pop("errors") for r in rows]
        for r in rows:
            print(f"# {name} prompt: " + json.dumps(r), flush=True)
        found = {
            "kv_dtype": eng.kv_dtype,
            "prompts": len(rows), "positions": sum(r["new"] for r in rows),
            "argmax_agree": sum(r["argmax_agree"] for r in rows),
            "logit_rms_err": olmoe_cell.rms(errors),
            "logit_max_abs_err": max(r["logit_abs_err"] for r in rows),
            "worst_gap": max(r["worst_gap"] for r in rows),
            "by_prompt": [[r["prompt"], r["logit_rms_err"]] for r in rows],
            "logit_std": float(np.mean([r["logit_std"] for r in rows])),
            "chunked": max(r["prefill_chunks"] for r in rows),
            "compiles": eng.compile_counts()["mixed"],
            "nonfinite_logit_steps": stats["nonfinite_logit_steps"]}
        found["why_incorrect"] = qwen3next_cell.verdict(found, conf["check"])
        out["variants"][name] = found
        eng.pool = None             # the next variant's pools need the room
        if eng is not base:
            eng.close()
    base.close()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
