"""Flash-attention sweep on the chip: the table the gate's and the
block rule's constants cite (`kernels/flash_attention.py`:
`flash_profitable`, `choose_flash_blocks`, `_BLOCK_TABLE`).

Two parts, both bf16, causal, on the device JAX finds (a tpu, or JAX
fails at start-up: a CPU timing of a TPU kernel is nobody's number):

  blocks  each of the three kernels alone at one shape, over block
          shapes (block_q, block_k): which blocks the table should hold
  gate    forward + backward of the packed flash path against the XLA
          path of `MultiHeadAttention._attend`, over (b, h, d, seq):
          where flash wins, and whether `flash_profitable` says so

  python tools/flash_sweep.py [--part blocks|gate|all]
      [--shape 1,2048,32,64] [-o chiprun_out/flash_sweep_tpu.json]

Reference analog: per-shape cuDNN algorithm selection
(/root/reference/src/ops/conv_2d.cu:173-260) — measured, not folklore.
"""

import argparse
import json
import os
import sys
import time
from datetime import datetime, timezone

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from flexflow_tpu.kernels import flash_attention as fa  # noqa: E402

BLOCKS = [(128, 128), (256, 256), (256, 512), (512, 256), (512, 512),
          (256, 1024), (1024, 256), (512, 1024), (1024, 512), (1024, 1024)]
# (b, h, d): OPT-1.3B's heads, OLMoE's, 32-wide heads, an odd head count
GATE_HEADS = [(1, 32, 64), (2, 16, 128), (1, 64, 32), (1, 3, 64)]
GATE_SEQS = [256, 512, 1024, 2048, 4096]


def xla_attention(q, k, v, causal):
    """The XLA path of ops/attention.py::_attend."""
    d = q.shape[-1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / np.sqrt(d)
    if causal:
        lq, lk = logits.shape[-2], logits.shape[-1]
        logits = jnp.where(jnp.tril(jnp.ones((lq, lk), bool)), logits,
                           -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def timed_us(f, args, iters=20):
    """Microseconds a call, the device's queue drained at both ends."""
    jax.block_until_ready(f(*args))
    jax.block_until_ready(f(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        y = f(*args)
    jax.block_until_ready(y)
    return round((time.perf_counter() - t0) / iters * 1e6, 1)


CHAIN = 8    # a model's layers: calls a dispatch, so that the host's
             # half millisecond a dispatch does not floor a kernel's time


def chained(f, feed):
    """CHAIN calls of f in ONE dispatch, each fed the one before it
    (`feed(args, out) -> args`): a kernel's time is value-blind."""
    def run(*args):
        for _ in range(CHAIN):
            out = f(*args)
            args = feed(args, out)
        return out
    return jax.jit(run)


def attempt(row, key, f, args, per=1):
    try:
        row[key] = round(timed_us(f, args) / per, 1)
    except Exception as e:  # a shape the compiler refuses is a finding
        row[key] = None
        row[key + "_error"] = str(e)[-300:]


def core_flops(b, h, sq, sk, d, causal):
    """One (sq, sk, d) product a head, counted from the shapes; causal
    halves what has to be computed."""
    return 2.0 * b * h * sq * sk * d * (0.5 if causal else 1.0)


def sweep_blocks(b, s, h, d, causal=True):
    rng = np.random.RandomState(0)
    q, k, v, do = (jnp.asarray(rng.randn(b, s, h * d) * 0.5, jnp.bfloat16)
                   for _ in range(4))
    g = fa._slab_geometry(h, d)[0]
    kw = dict(heads=h, causal=causal, scale=1.0 / d ** 0.5, interpret=False)
    o, lse = fa.flash_fwd(q, k, v, block_q=128, block_k=128, **kw)
    delta = jnp.zeros_like(lse)
    unit = core_flops(b, h, s, s, d, causal)
    rows = []
    for bq, bk in BLOCKS:
        if s % bq or s % bk:
            continue
        row = {"b": b, "h": h, "sq": s, "sk": s, "d": d, "causal": causal,
               "block_q": bq, "block_k": bk, "slab_heads": g}
        bl = dict(block_q=bq, block_k=bk, **kw)
        attempt(row, "fwd_us", chained(
            lambda *a: fa.flash_fwd(*a, **bl),
            lambda a, out: (out[0],) + a[1:]), (q, k, v), CHAIN)
        attempt(row, "dq_us", chained(
            lambda *a: fa.flash_bwd_dq(*a, **bl),
            lambda a, out: (out,) + a[1:]),
            (q, k, v, do, lse, delta), CHAIN)
        attempt(row, "dkv_us", chained(
            lambda *a: fa.flash_bwd_dkv(*a, **bl),
            lambda a, out: a[:1] + tuple(out) + a[3:]),
            (q, k, v, do, lse, delta), CHAIN)
        # products a kernel has to make (QK^T, PV | QK^T, dO.V^T, dS.K |
        # QK^T, dO.V^T, P^T.dO, dS^T.Q), over its time
        for key, n in (("fwd", 2), ("dq", 3), ("dkv", 4)):
            if row.get(key + "_us"):
                row[key + "_tflops"] = round(
                    n * unit / row[key + "_us"] / 1e6, 2)
        print(row, flush=True)
        rows.append(row)
    return rows


def sweep_gate(causal=True):
    rng = np.random.RandomState(0)
    rows = []
    for b, h, d in GATE_HEADS:
        for s in GATE_SEQS:
            q, k, v = (jnp.asarray(rng.randn(b, s, h, d) * 0.5, jnp.bfloat16)
                       for _ in range(3))

            def loss_f(q, k, v):
                return jnp.sum(fa.flash_attention_bshd(
                    q, k, v, causal=causal).astype(jnp.float32))

            def loss_x(q, k, v):
                return jnp.sum(xla_attention(q, k, v, causal)
                               .astype(jnp.float32))

            row = {"b": b, "h": h, "sq": s, "sk": s, "d": d,
                   "causal": causal,
                   "blocks": fa.choose_flash_blocks(
                       s, s, fa._slab_geometry(*fa._lane_pad(h, d))[0]),
                   "gate_says_flash": bool(
                       fa.flash_unsupported(s, s, d) is None
                       and fa.flash_profitable(b, h, s, s, d))}
            feed = lambda a, out: out  # noqa: E731 (dq, dk, dv) -> q, k, v
            attempt(row, "flash_fwdbwd_us", chained(
                jax.grad(loss_f, argnums=(0, 1, 2)), feed), (q, k, v), CHAIN)
            attempt(row, "xla_fwdbwd_us", chained(
                jax.grad(loss_x, argnums=(0, 1, 2)), feed), (q, k, v), CHAIN)
            fl, xl = row["flash_fwdbwd_us"], row["xla_fwdbwd_us"]
            if fl and xl:
                # within a twentieth of each other is a tie: either
                # answer of the gate is right
                row["flash_wins"] = None if abs(fl - xl) < 0.05 * max(
                    fl, xl) else fl < xl
                row["gate_correct"] = row["flash_wins"] in (
                    None, row["gate_says_flash"])
            print(row, flush=True)
            rows.append(row)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--part", default="all",
                    choices=("blocks", "gate", "all"))
    ap.add_argument("--shape", default="1,2048,32,64",
                    help="b,seq,heads,head_dim of the blocks part")
    ap.add_argument("-o", "--out", default=os.path.join(
        "chiprun_out", "flash_sweep_tpu.json"))
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"flash_sweep: the kernels compile for a tpu; this "
                 f"device is {dev.platform!r}")
    out = {"platform": dev.platform, "device": str(dev.device_kind),
           "captured": datetime.now(timezone.utc).strftime(
               "%Y-%m-%dT%H:%M:%SZ")}
    if args.part in ("blocks", "all"):
        out["blocks"] = sweep_blocks(*map(int, args.shape.split(",")))
    if args.part in ("gate", "all"):
        out["gate"] = sweep_gate()
        mis = [r for r in out["gate"] if r.get("gate_correct") is False]
        if mis:
            print(f"GATE MISPREDICTS {len(mis)} shapes — re-tune "
                  f"flash_profitable:", *mis, sep="\n")
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
