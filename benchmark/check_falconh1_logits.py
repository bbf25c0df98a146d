#!/usr/bin/env python3
"""The Falcon-H1 engine against its reference on LOGITS, outside any
window.

    python3 benchmark/check_falconh1_logits.py --seed <n> [--variants base,fp8_pages,...] [--longest 2100] [--sizes]

At the configuration's published widths on the chip (`--rehearse-cpu`:
its rehearsal size, kernels interpreted): seeded prompts of 16 to
`--longest` tokens at the cell's own lengths — one prefilled whole, one
in two chunks, two TOGETHER (so the longer one's chunks do not start at
multiples of the prefill budget, its runs share blocks of lanes with the
other's, and two state slots are live at once), a mean prompt of the
cell and its longest — are prefilled, then decoded `--new` (64) tokens
through pages, state slots and tails, and the engine's top-k logits at
every generated position are compared with lib/reference_falconh1.py's
full forward pass. Printed per prompt: the root mean square and the
largest of the logit differences (what `logit_rms` limits) and the worst
gap (the reference's best logit minus its logit of the token the engine
chose, what `logit_margin` limits); per variant both numbers over all
the prompts, with the cell's verdict. `--sizes` prints first the
reference's root mean square of the stream and of the three branches at
every layer (what the configuration's `init` is held to).

`--variants` serves the SAME weights again, each of which ought to fail
a limit:
  fp8_pages        kv_dtype float8_e4m3 for the six layers' pages: the
                   precision below the stated bf16;
  wrong_page       a planted fault: once a sequence's prompt is in, its
                   first page holds its second page's keys and values in
                   every layer;
  state_swap       a planted fault: once two sequences decode together,
                   their state slots are exchanged in every layer;
  ssm_out_mult_1   `ssm_out_multiplier` left at 1;
  key_mult_1       `key_multiplier` left at 1;
  group0_for_all   every head reads group 0's B and C;
  whole_norm       the gated norm's statistics over all d_ssm channels
                   at once, not a group's;
  attn_after_ssm   the attention branch reads the norm of the stream
                   AFTER the Mamba-2 branch was added, not `h`.
The last five are faults of the PROGRAM's mathematics: the engine is
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

CONFIG = "falcon-h1-34b-1chip-l6.json"
VARIANTS = ("base", "fp8_pages", "wrong_page", "state_swap",
            "ssm_out_mult_1", "key_mult_1", "group0_for_all", "whole_norm",
            "attn_after_ssm")


def plant_state_swap(eng):
    """-> on_step(session, event): once two requests decode in one step,
    their slots' states are exchanged in every layer (once)."""
    import jax
    done = []
    # in place, a row set a call: a second copy of the 2.3 GiB slab does
    # not fit, and XLA copies it where one program reads both rows and
    # writes both
    rows = jax.jit(lambda slab, a: slab[:, a])
    put = jax.jit(lambda slab, a, rows: slab.at[:, a].set(rows),
                  donate_argnums=0)

    def swap(slab, a, b):
        rows_a, rows_b = rows(slab, a), rows(slab, b)
        return put(put(slab, a, rows_b), b, rows_a)

    def on_step(session, ev):
        decoding = [ch.req for ch in (ev.plan.chunks if ev.plan else ())
                    if ch.is_decode]
        if done or len(decoding) < 2:
            return
        done.append(True)
        a, b = decoding[0].slot, decoding[1].slot
        eng.pool = dataclasses.replace(
            eng.pool, state=swap(eng.pool.state, a, b))

    return on_step


@contextlib.contextmanager
def faulty_program(name):
    """The program with ONE piece of its mathematics at fault, while an
    engine is built and traced under it."""
    import jax.numpy as jnp
    from flexflow_tpu.models.falcon_h1 import SSD_ATTN
    from flexflow_tpu.ops import ssd as SD
    from flexflow_tpu.ops.common import rotary
    from flexflow_tpu.serve import mixers
    from flexflow_tpu.serve.arch import FalconH1, _project
    if name == "ssm_out_mult_1":
        def ssd_out(self, params, i, y, z):
            m = self.ssd
            return SD.gate_and_project(params[f"layer{i}_ssm"], y, z,
                                       m.dims, m.eps, 1.0)
        patches = [(FalconH1, "ssd_out", ssd_out)]
    elif name == "whole_norm":
        def ssd_out(self, params, i, y, z):
            m = self.ssd
            # ONE group for the norm: all d_ssm channels at once
            return SD.gate_and_project(params[f"layer{i}_ssm"], y, z,
                                       m.dims._replace(groups=1), m.eps,
                                       m.out_multiplier)
        patches = [(FalconH1, "ssd_out", ssd_out)]
    elif name == "key_mult_1":
        def qkv(self, params, i, h, positions, lora=None):
            q, k, v = _project(params[f"layer{i}_attn"],
                               h * self.attention_in_multiplier)
            return (rotary(q, positions, self.rope_theta),
                    rotary(k, positions, self.rope_theta), v)
        patches = [(FalconH1, "qkv", qkv)]
    elif name == "group0_for_all":
        real = FalconH1.ssd_scan_inputs

        def ssd_scan_inputs(self, params, i, u, dt):
            (v, b, c, la), skip = real(self, params, i, u, dt)
            first = lambda a: jnp.broadcast_to(a[:, :1], a.shape)
            return (v, first(b), first(c), la), skip
        patches = [(FalconH1, "ssd_scan_inputs", ssd_scan_inputs)]
    elif name == "attn_after_ssm":
        def sequential(g, params, i, x, h, lanes, pool, memory, lora=None,
                       tp_axis=None):
            s, pool = mixers._ssd(g, params, i, h, lanes, pool)
            a, pool, memory = mixers._attention(
                g, params, i, x, g.arch.norm1(params, i, x + s), lanes,
                pool, memory, lora, tp_axis)
            return x + (s + a), pool, memory
        saved = mixers.BODIES[SSD_ATTN]
        mixers.BODIES[SSD_ATTN] = sequential
        try:
            yield
        finally:
            mixers.BODIES[SSD_ATTN] = saved
        return
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
    from lib import falconh1_cell, olmoe_cell
    if (jax.devices()[0].platform == "tpu") == args.rehearse_cpu:
        raise SystemExit("check_falconh1_logits: a TPU, or --rehearse-cpu")
    arm_compile_cache()
    base, _ = falconh1_cell.build_engine(conf, args.seed,
                                         args.rehearse_cpu, warm=False)
    reference = falconh1_cell.reference_logits(conf)
    rng = np.random.default_rng([args.seed, 5])
    scale = 16 if args.rehearse_cpu else 1
    budget = base.prefill_budget

    def toks(n):
        return rng.integers(1, conf["vocab_size"], max(4, n)).tolist()

    if args.sizes:
        print("# branch_sizes [stream, ssm, attention, feed-forward] a "
              "layer: " + json.dumps(falconh1_cell.branch_sizes(
                  base.params, conf, toks(448 // scale))), flush=True)
    groups = [[toks(16)], [toks(300 // scale)],
              [toks(budget + 190 // scale)],
              # together: the second's chunks start off the budget's
              # multiples, their runs share blocks of lanes, and two
              # slots hold state at once
              [toks(budget + 188 // scale), toks(2 * budget + 200 // scale)],
              [toks(448 // scale)], [toks(args.longest // scale)]]

    def variant(name):
        """(the engine, its on_step) of a variant over the SAME model."""
        if name == "base":
            return base, None
        if name == "wrong_page":
            return base, plant_wrong_page(base)
        if name == "state_swap":
            return base, plant_state_swap(base)
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
        rows, stats = falconh1_cell.logits_through_cache(
            eng, conf, groups, args.new, on_step, reference)
        errors = [r.pop("errors") for r in rows]
        for r in rows:
            print(f"# {name} prompt: " + json.dumps(r), flush=True)
        found = {
            "kv_dtype": eng.kv_dtype, "scan_impl": eng.scan_impl,
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
        found["why_incorrect"] = falconh1_cell.verdict(found,
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
