#!/usr/bin/env python3
"""The Olmo-Hybrid engine against its reference on LOGITS, outside any
window.

    python3 benchmark/check_olmohybrid_logits.py --seed <n> [--variants base,fp8_pages,wrong_page,beta_not_doubled,no_correction,pre_norm,qk_norm_per_head] [--longest 12000]

At the configuration's published widths on the chip (`--rehearse-cpu`:
its rehearsal size, kernels interpreted): seeded prompts of 16 to
`--longest` tokens — one prefilled whole, one in two chunks, two
TOGETHER (so the longer one's chunks do not start at multiples of the
prefill budget and its runs share blocks of lanes with the other's), a
mean prompt of the cell and a long one — are prefilled, then decoded
`--new` (64) tokens through pages, state slots and tails, and the
engine's top-k logits at every generated position are compared with
lib/reference_olmohybrid.py's full forward pass. Printed per prompt: the
root mean square and the largest of the logit differences (what
`logit_rms` limits) and the worst gap (the reference's best logit minus
its logit of the token the engine chose, what `logit_margin` limits);
per variant both numbers over all the prompts, with the cell's verdict.

`--variants` serves the SAME weights again, each of which ought to fail
a limit:
  fp8_pages         kv_dtype float8_e4m3 for the four full layers'
                    pages: the precision below the stated bf16;
  wrong_page        a planted fault: once a sequence's prompt is in, its
                    first page holds its second page's keys and values
                    in every full layer;
  beta_not_doubled  beta = sigmoid(b): `linear_allow_neg_eigval` left
                    out;
  no_correction     the delta rule without its correction term
                    (S += k (beta v)^T: plain gated linear attention),
                    in the step's lane and chunk forms alike;
  pre_norm          the block's norms BEFORE their sub-layer (x + f(N(x))
                    in place of x + N(f(x))), with the same scales;
  qk_norm_per_head  q and k normed a head of 128 at a time, not over
                    the whole projection.
The last four are faults of the PROGRAM's mathematics: the engine is
built again from the same weights with the faulty function in the
program's place (the reference is never touched; the delta kernel takes
beta and the state as data, so the first two of them reach it too —
`no_correction` replaces the twin's `_token` and `_chunk` and runs the
lanes on the twin). The last line is one JSON object: a reading per
variant.
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

CONFIG = "olmo-hybrid-7b-1chip-l16.json"
VARIANTS = ("base", "fp8_pages", "wrong_page", "beta_not_doubled",
            "no_correction", "pre_norm", "qk_norm_per_head")


def plant_wrong_page(eng):
    """-> on_step(session, event): once a request's prompt is in, its
    first page holds its second page's keys and values in the full
    layers."""
    import jax
    planted = set()
    ps = eng.cache_cfg.page_size
    # in place: a second copy of a 2.4 GB leaf does not fit beside it
    move = jax.jit(lambda pages, dst, src: pages.at[:, dst].set(
        pages[:, src]), donate_argnums=0)

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
                    full, k=move(full.k, dst, src),
                    v=move(full.v, dst, src)))

    return on_step


@contextlib.contextmanager
def faulty_program(name):
    """The program with ONE piece of its mathematics at fault, while an
    engine is built and traced under it. -> the engine's extra keyword
    arguments."""
    import jax
    import jax.numpy as jnp
    from flexflow_tpu.kernels import gated_delta_scan as KD
    from flexflow_tpu.ops import gated_delta as GD
    from flexflow_tpu.ops.common import rms_norm
    from flexflow_tpu.serve.arch import OlmoHybrid
    if name == "beta_not_doubled":
        gates = GD.gates
        patches = [(GD, "gates", lambda p, b, a, beta_scale=1.0:
                    gates(p, b, a, 1.0))]
    elif name == "no_correction":
        from check_qwen3next_logits import faulty_program as qwen
        with qwen("no_correction"):
            # the lanes on the twin, whose token and chunk are at fault
            saved = KD.supported
            KD.supported = lambda *a: False
            try:
                yield
            finally:
                KD.supported = saved
        return
    elif name == "pre_norm":
        # x + f(N(x)) in place of x + N(f(x)): the same scales, the norm
        # on the other side of the sub-layer
        real = OlmoHybrid.branch_norm

        def norm1(self, params, i, x):
            return real(self, params, i, 1, x)

        def ffn(self, params, i, x, live=None, psum_axis=None, lora=None):
            from flexflow_tpu.ops.gated import gated_ffn
            with jax.named_scope("ffn"):
                return gated_ffn(params[f"layer{i}_mlp"],
                                 real(self, params, i, 2, x)), None

        patches = [(OlmoHybrid, "norm1", norm1), (OlmoHybrid, "ffn", ffn),
                   (OlmoHybrid, "branch_norm",
                    lambda self, params, i, which, y: y)]
    elif name == "qk_norm_per_head":
        def qkv(self, params, i, h, positions, lora=None):
            from flexflow_tpu.serve.arch import _project
            p = params[f"layer{i}_attn"]
            q, k, v = _project(p, h)
            # statistics over a head's dims alone, the same scales
            per_head = lambda a, w: jnp.stack([
                rms_norm(a[..., j, :], w[j], self.ln_eps)
                for j in range(a.shape[-2])], axis=-2)
            return per_head(q, p["q_norm"]), per_head(k, p["k_norm"]), v

        patches = [(OlmoHybrid, "qkv", qkv)]
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
    ap.add_argument("--longest", type=int, default=12000)
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
    from lib import olmoe_cell, olmohybrid_cell
    if (jax.devices()[0].platform == "tpu") == args.rehearse_cpu:
        raise SystemExit("check_olmohybrid_logits: a TPU, or --rehearse-cpu")
    arm_compile_cache()
    base, _ = olmohybrid_cell.build_engine(conf, args.seed,
                                           args.rehearse_cpu, warm=False)
    reference = olmohybrid_cell.reference_logits(conf)
    rng = np.random.default_rng([args.seed, 5])
    scale = 32 if args.rehearse_cpu else 1
    budget = base.prefill_budget

    def toks(n):
        return rng.integers(1, conf["vocab_size"], max(4, n)).tolist()

    groups = [[toks(16)], [toks(300 // scale)],
              [toks(budget + 190 // scale)],
              # together: the second's chunks start off the budget's
              # multiples, and their runs share blocks of lanes
              [toks(budget + 188 // scale), toks(3 * budget + 808 // scale)],
              [toks(2344 // scale)], [toks(args.longest // scale)]]

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
        rows, stats = olmohybrid_cell.logits_through_cache(
            eng, conf, groups, args.new, on_step, reference)
        errors = [r.pop("errors") for r in rows]
        for r in rows:
            print(f"# {name} prompt: " + json.dumps(r), flush=True)
        found = {
            "kv_dtype": eng.kv_dtype,
            "delta_impl": eng.geometry.delta_impl,
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
        found["why_incorrect"] = olmohybrid_cell.verdict(found,
                                                         conf["check"])
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
