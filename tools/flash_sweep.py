"""Flash-attention dispatch-threshold sweep.

Measures the Pallas flash kernel vs the XLA einsum path, fwd+bwd, over
the (seq, head_dim) grid the `flash_profitable` gate
(kernels/flash_attention.py) claims to encode, and writes the table to
evidence/ — the committed artifact behind the heuristic's constants.
Reference analog: per-shape cuDNN algorithm selection
(/root/reference/src/ops/conv_2d.cu:173-260) — measured, not folklore.

  FLASH_SWEEP_PLATFORM=tpu python tools/flash_sweep.py   # on-chip
"""

import json
import os
import sys
import time
from datetime import datetime, timezone

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _platform import select_platform  # noqa: E402

_plat = select_platform("FLASH_SWEEP_PLATFORM")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from flexflow_tpu.kernels.flash_attention import (  # noqa: E402
    flash_attention_bshd, flash_profitable)

B, H = 8, 8  # the bench transformer's batch/head scale


def xla_attention(q, k, v, causal):
    d = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    logits = logits / jnp.sqrt(jnp.asarray(d, jnp.float32))
    if causal:
        lq, lk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((lq, lk), bool))
        logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def timed(f, args, iters=8):
    y = f(*args)
    jnp.ravel(jax.tree_util.tree_leaves(y)[0])[0].item()  # sync
    t0 = time.perf_counter()
    for _ in range(iters):
        y = f(*args)
    jnp.ravel(jax.tree_util.tree_leaves(y)[0])[0].item()
    return (time.perf_counter() - t0) / iters


def main():
    interpret = _plat != "tpu"
    rows = []
    grid = [(s, d, c) for s in (512, 1024, 2048) for d in (64, 128)
            for c in (False, True)]
    if interpret:
        grid = [(256, 128, False)]  # smoke-scale off-chip
    rng = np.random.RandomState(0)
    for sq, d, causal in grid:
        q, k, v = (jnp.asarray(rng.randn(B, sq, H, d) * 0.1, jnp.bfloat16)
                   for _ in range(3))

        def loss_f(q, k, v):
            return jnp.sum(flash_attention_bshd(
                q, k, v, causal=causal,
                interpret=interpret).astype(jnp.float32))

        def loss_x(q, k, v):
            return jnp.sum(xla_attention(q, k, v, causal).astype(
                jnp.float32))

        row = {"b": B, "h": H, "sq": sq, "sk": sq, "d": d,
               "causal": causal,
               "gate_says_flash": flash_profitable(B, H, sq, sq, d)}
        try:
            row["flash_fwdbwd_us"] = round(timed(
                jax.jit(jax.grad(loss_f, argnums=(0, 1, 2))),
                (q, k, v)) * 1e6)
        except Exception as e:  # unsupported shape -> XLA is the only path
            row["flash_fwdbwd_us"] = None
            row["flash_error"] = str(e)[:100]
        if d < 128:
            # the d=64 decider: pad_lanes=False hands Mosaic the raw
            # head_dim, halving the kernel's dot FLOPs vs the always-
            # safe 128-lane padding — the arm that could flip the gate
            # for the bench transformer (h512/8 heads -> d=64)
            def loss_np(q, k, v):
                return jnp.sum(flash_attention_bshd(
                    q, k, v, causal=causal, pad_lanes=False,
                    interpret=interpret).astype(jnp.float32))
            try:
                row["flash_nopad_fwdbwd_us"] = round(timed(
                    jax.jit(jax.grad(loss_np, argnums=(0, 1, 2))),
                    (q, k, v)) * 1e6)
            except Exception as e:
                row["flash_nopad_fwdbwd_us"] = None
                row["flash_nopad_error"] = str(e)[:100]
        row["xla_fwdbwd_us"] = round(timed(
            jax.jit(jax.grad(loss_x, argnums=(0, 1, 2))), (q, k, v)) * 1e6)
        # gate_correct judges ONLY the shipped (padded) dispatch the
        # gate controls; the nopad arm gets its own key so a would-be
        # win by a non-dispatchable kernel reads as a retune
        # OPPORTUNITY, not a gate error
        if row["flash_fwdbwd_us"] is not None:
            row["flash_wins"] = row["flash_fwdbwd_us"] < row["xla_fwdbwd_us"]
            row["gate_correct"] = row["flash_wins"] == row["gate_says_flash"]
        if row.get("flash_nopad_fwdbwd_us") is not None:
            row["flash_nopad_wins"] = (row["flash_nopad_fwdbwd_us"]
                                       < row["xla_fwdbwd_us"])
        print(row, flush=True)
        rows.append(row)
    out = {"platform": _plat,
           "device": str(jax.devices()[0].device_kind),
           "captured": datetime.now(timezone.utc).strftime(
               "%Y-%m-%dT%H:%M:%SZ"),
           "rows": rows}
    path = os.path.join(os.path.dirname(__file__), "..", "evidence",
                        f"flash_sweep_{_plat}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {os.path.normpath(path)}")
    mis = [r for r in rows if r.get("gate_correct") is False]
    if mis:
        print(f"GATE MISPREDICTS {len(mis)} shapes — re-tune "
              f"flash_profitable:", *mis, sep="\n")
    opp = [r for r in rows
           if r.get("flash_nopad_wins") and not r.get("gate_says_flash")]
    if opp:
        print(f"NOPAD OPPORTUNITY on {len(opp)} shapes — the d<128 "
              f"pad_lanes=False kernel beats XLA where the shipped "
              f"gate stays off:", *opp, sep="\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
