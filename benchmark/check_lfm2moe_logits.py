#!/usr/bin/env python3
"""The LFM2-MoE engine against its reference on LOGITS, outside any
window.

    python3 benchmark/check_lfm2moe_logits.py --seed <n> [--variants base,fp8_pages,...] [--longest 2100] [--new 64] [--sizes]

At the configuration's published widths on the chip (`--rehearse-cpu`:
its rehearsal size, kernels interpreted): seeded prompts of 16 to
`--longest` tokens at the cell's own lengths — one prefilled whole, one
in two chunks, one whose second chunk is ONE token (a chunk that ends one
token into a run), two TOGETHER (so the longer one's chunks do not start
at multiples of the prefill budget and two slots' tails are live at
once), a mean prompt of the cell and its longest — are prefilled, then
decoded `--new` (64) tokens through pages and tails, and the engine's
top-k logits at every generated position are compared with
lib/reference_lfm2moe.py's full forward pass. Printed per prompt: the
root mean square and the largest of the logit differences (what
`logit_rms` limits) and the worst gap (what `logit_margin` limits); per
variant both numbers over all the prompts, with the cell's verdict.
`--sizes` prints first the reference's root mean square of the stream
and of the two branches at every layer, the first router's logit
deviation and the share of tokens whose four experts the selection bias
changes (what the configuration's `init` is held to).

`--variants` serves the SAME weights again, each of which ought to fail
a limit:
  fp8_pages         kv_dtype float8_e4m3 for the two attention layers'
                    pages: the precision below the stated bf16;
  wrong_page        a planted fault: once a sequence's prompt is in, its
                    first page holds its second page's keys and values in
                    both attention layers;
  tail_swap         a planted fault: once two sequences decode together,
                    their tail rows are exchanged in every layer;
  tail_zeroed       a run reads zeros for its slot's tail: the tail lost
                    at every chunk boundary and decode step;
  tail_holds_h      the tail keeps the layer's normed input `h` in place
                    of the product B * z;
  gates_swapped     B and C exchanged;
  silu_after_taps   a silu between the taps and the second gate;
  bias_in_weights   the selection bias added to the WEIGHTS as well as
                    to the choice;
  no_bias           the selection bias left out;
  softmax_scores    softmax over the experts in place of sigmoid;
  no_renorm         the four weights not renormalised;
  qk_norm_whole     the QK-norm's statistics over the whole projection,
                    not a head's 64 dims;
  dense_as_experts  layers 0-1 given an expert layer (the first routing
                    layer's experts) in place of their dense feed-forward.
From `tail_zeroed` on these are faults of the PROGRAM's mathematics: the
engine is built again from the same weights with the faulty function in
the program's place (the reference is never touched). The last line is
one JSON object: a reading per variant.
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

CONFIG = "lfm2-24b-a2b-1chip-l10.json"
VARIANTS = ("base", "fp8_pages", "wrong_page", "tail_swap", "tail_zeroed",
            "tail_holds_h", "gates_swapped", "silu_after_taps",
            "bias_in_weights", "no_bias", "softmax_scores", "no_renorm",
            "qk_norm_whole", "dense_as_experts")


def plant_tail_swap(eng):
    """-> on_step(session, event): once two requests decode in one step,
    their slots' tail rows are exchanged in every layer (once)."""
    done = []

    def on_step(session, ev):
        decoding = [ch.req for ch in (ev.plan.chunks if ev.plan else ())
                    if ch.is_decode]
        if done or len(decoding) < 2:
            return
        done.append(True)
        a, b = decoding[0].slot, decoding[1].slot
        tail = eng.pool.tail
        rows_a, rows_b = tail[:, a], tail[:, b]
        eng.pool = dataclasses.replace(
            eng.pool, tail=tail.at[:, a].set(rows_b).at[:, b].set(rows_a))

    return on_step


@contextlib.contextmanager
def faulty_program(name):
    """The program with ONE piece of its mathematics at fault, while an
    engine is built and traced under it."""
    import jax
    import jax.numpy as jnp
    from flexflow_tpu.models.lfm2_moe import CONV
    from flexflow_tpu.ops import short_conv as SC
    from flexflow_tpu.ops import ssm
    from flexflow_tpu.ops.common import rotary
    from flexflow_tpu.ops.moe import route_top_k
    from flexflow_tpu.serve import arch as A
    from flexflow_tpu.serve import mixers
    patches = []
    if name == "tail_zeroed":
        def segmented(p, b, c, z, tail, *lanes):
            conv, _ = ssm.segmented_conv(p, SC.gate_in(b, z),
                                         jnp.zeros_like(tail), *lanes)
            _, tail = ssm.segmented_conv(p, SC.gate_in(b, z), tail, *lanes)
            return SC.gate_out(c, conv), tail
        patches = [(SC, "segmented", segmented)]
    elif name == "tail_holds_h":
        real = mixers.BODIES[CONV]

        def keeps_h(g, params, i, x, h, lanes, pool, memory, lora=None,
                    tp_axis=None):
            j = g.arch.conv_layers.index(i)
            x, new, memory = real(g, params, i, x, h, lanes, pool, memory)
            # the write-back again, of h where the product stood
            _, tail = ssm.segmented_conv(
                params[f"layer{i}_conv"], h, pool.tail[j], lanes.lane_slots,
                lanes.positions, lanes.offsets, lanes.tail_lanes)
            return x, dataclasses.replace(
                new, tail=new.tail.at[j].set(tail)), memory
        patches = [(mixers.BODIES, CONV, keeps_h)]
    elif name == "gates_swapped":
        real = SC.project

        def project(p, h):
            b, c, z = real(p, h)
            return c, b, z
        patches = [(SC, "project", project)]
    elif name == "silu_after_taps":
        real = SC.gate_out
        patches = [(SC, "gate_out",
                    lambda c, conv: real(c, jax.nn.silu(conv)))]
    elif name in ("bias_in_weights", "no_bias", "softmax_scores",
                  "no_renorm"):
        def route(tokens, gate_w, k, norm_topk, score="softmax",
                  bias=None):
            if name == "no_bias":
                return route_top_k(tokens, gate_w, k, norm_topk, score)
            if name == "softmax_scores":
                return route_top_k(tokens, gate_w, k, norm_topk,
                                   "softmax", bias)
            if name == "no_renorm":
                return route_top_k(tokens, gate_w, k, False, score, bias)
            probs, _, assign = route_top_k(tokens, gate_w, k, norm_topk,
                                           score, bias)
            vals = jnp.take_along_axis(
                probs + bias.astype(jnp.float32), assign, axis=-1)
            return probs, vals / (
                jnp.sum(vals, axis=-1, keepdims=True) + 1e-6), assign
        patches = [(A, "route_top_k", route)]
    elif name == "qk_norm_whole":
        def qkv(self, params, i, h, positions, lora=None):
            p = params[f"layer{i}_attn"]
            q, k, v = A._project(p, h)

            def whole(a, w):
                af = a.astype(jnp.float32)
                var = jnp.mean(jnp.square(af), axis=(-2, -1), keepdims=True)
                return (af * jax.lax.rsqrt(var + self.ln_eps)
                        * w.astype(jnp.float32)).astype(a.dtype)
            return (rotary(whole(q, p["q_norm"]), positions,
                           self.rope_theta),
                    rotary(whole(k, p["k_norm"]), positions,
                           self.rope_theta), v)
        patches = [(A.LFM2MoE, "qkv", qkv)]
    elif name == "dense_as_experts":
        real = A.LFM2MoE.ffn

        def ffn(self, params, i, x, live=None, psum_axis=None, lora=None):
            if i >= self.dense_layers:
                return real(self, params, i, x, live, psum_axis, lora)
            first = self.dense_layers
            borrowed = {**params,
                        f"layer{first}_ffn_norm": params[f"layer{i}_ffn_norm"]}
            y, _ = real(self, borrowed, first, x, live, psum_axis, lora)
            return y, None
        patches = [(A.LFM2MoE, "ffn", ffn)]
    else:
        raise SystemExit(f"no variant {name!r}")
    get = lambda obj, key: obj[key] if isinstance(obj, dict) \
        else obj.__dict__[key]
    put = lambda obj, key, val: obj.__setitem__(key, val) \
        if isinstance(obj, dict) else setattr(obj, key, val)
    saved = [(obj, key, get(obj, key)) for obj, key, _ in patches]
    for obj, key, new in patches:
        put(obj, key, new)
    try:
        yield
    finally:
        for obj, key, old in saved:
            put(obj, key, old)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--variants", default="base")
    ap.add_argument("--new", type=int, default=64)
    ap.add_argument("--longest", type=int, default=2100)
    ap.add_argument("--sizes", action="store_true")
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
    from check_olmohybrid_logits import plant_wrong_page
    from lib import lfm2moe_cell, olmoe_cell
    if (jax.devices()[0].platform == "tpu") == args.rehearse_cpu:
        raise SystemExit("check_lfm2moe_logits: a TPU, or --rehearse-cpu")
    arm_compile_cache()
    base, _ = lfm2moe_cell.build_engine(conf, args.seed, args.rehearse_cpu,
                                        warm=False)
    reference = lfm2moe_cell.reference_logits(conf)
    rng = np.random.default_rng([args.seed, 5])
    scale = 16 if args.rehearse_cpu else 1
    budget = base.prefill_budget

    def toks(n):
        return rng.integers(1, conf["vocab_size"], max(4, n)).tolist()

    if args.sizes:
        seq = toks(448 // scale)
        print("# branch_sizes [stream, mixer, feed-forward] a layer: "
              + json.dumps(lfm2moe_cell.branch_sizes(base.params, conf,
                                                     seq)), flush=True)
        print("# router: " + json.dumps(lfm2moe_cell.router_readings(
            base.params, conf, seq)), flush=True)
    groups = [[toks(16)], [toks(300 // scale)],
              [toks(budget + 190 // scale)],
              # a second chunk of ONE token: the run resumes from the
              # tail a whole chunk left, for a single lane
              [toks(budget + 1)],
              # together: the second's chunks start off the budget's
              # multiples, and two slots hold tails at once
              [toks(budget + 188 // scale), toks(2 * budget + 200 // scale)],
              [toks(320 // scale)], [toks(args.longest // scale)]]

    def variant(name):
        """(the engine, its on_step) of a variant over the SAME model."""
        if name == "base":
            return base, None
        if name == "wrong_page":
            return base, plant_wrong_page(base)
        if name == "tail_swap":
            return base, plant_tail_swap(base)
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
        rows, stats = lfm2moe_cell.logits_through_cache(
            eng, conf, groups, args.new, on_step, reference)
        errors = [r.pop("errors") for r in rows]
        for r in rows:
            print(f"# {name} prompt: " + json.dumps(r), flush=True)
        found = {
            "kv_dtype": eng.kv_dtype,
            "expert_impl": eng.arch.expert_impl(eng.mixed_width),
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
        found["why_incorrect"] = lfm2moe_cell.verdict(found, conf["check"])
        out["variants"][name] = found
        print(f"# {name}: " + json.dumps(found), flush=True)
        eng.pool = None             # the next variant's pools need the room
        if eng is not base:
            eng.close()
    base.close()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
