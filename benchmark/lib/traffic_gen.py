"""The one traffic generator: a traffic file's parameters + a seed -> work.

Copied and cut down from flexflow_tpu/serve/traffic.py (`TrafficSpec`,
`make_traffic`: Poisson / bursty arrivals, Zipf tenants with shared
prefixes, clipped-Pareto lengths), so that the yardstick does not move
with the program; the original is listed in PERF.md for a later PR to
delete or keep as the router's own.

Steadiness rule (the builder's contract): every seed offers the SAME
requests of the same sizes at the same times — all drawn from the
traffic file's `sizes_seed` — and `--seed` makes the token ids (and the
weights). Dealing the sizes out in another order per seed was tried on
the chip (PR 23, chat-steady; PERF.md section 6): two runs of one seed
agreed within 0.4 %, six orders spread a tail by 16-29 % — the order,
not the system, then decides the 95th percentile.

Kinds of traffic (field `kind` of the file):
  requests   independent requests with arrival times (open loop)
  documents  documents each asked several times, `reuse_distance`
             other requests between two asks of one document (closed
             loop over one list)
  batches    token batches for training
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Req:
    index: int
    due_s: Optional[float]      # arrival time from the stream's start
    prompt: List[int]
    max_new: int
    tenant: int = 0
    doc: int = -1               # document id (documents kind)
    ask: int = 0                # which ask of its document


def _rng(seed: int, *tags: int):
    # SeedSequence takes any non-negative integer, 2**32 and over too
    return np.random.default_rng([int(seed), *map(int, tags)])


def clipped_pareto(rng, n: int, spec: dict) -> np.ndarray:
    """`n` lengths: lo + Pareto(a) scaled to the stated mean before
    clipping, rounded, clipped to [min, max]."""
    lo, hi = int(spec["min"]), int(spec["max"])
    a = float(spec.get("pareto_a", 2.0))
    mean = float(spec.get("mean", (lo + hi) / 2))
    scale = max(mean - lo, 0.0) * (a - 1.0 if a > 1.0 else 1.0)
    v = lo + rng.pareto(a, size=n) * scale
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def uniform_lengths(rng, n: int, spec: dict) -> np.ndarray:
    return rng.integers(int(spec["min"]), int(spec["max"]) + 1, size=n)


def _lengths(rng, n: int, spec: dict) -> np.ndarray:
    if spec.get("dist", "pareto") == "uniform":
        return uniform_lengths(rng, n, spec)
    return clipped_pareto(rng, n, spec)


def arrival_times(rng, n: int, t: dict) -> np.ndarray:
    """Poisson arrivals at `rate_rps`; `arrival: bursty` multiplies the
    rate by `burst_factor` inside windows of about `burst_len` requests
    and divides it by half that between them (as serve/traffic.py)."""
    rate = float(t["rate_rps"])
    if t.get("arrival", "poisson") == "poisson":
        return np.cumsum(rng.exponential(1.0 / rate, size=n))
    bf = float(t.get("burst_factor", 4.0))
    gaps, in_burst, left = [], False, 0
    for _ in range(n):
        if left <= 0:
            in_burst = not in_burst
            left = max(1, int(rng.poisson(int(t.get("burst_len", 8)))))
        left -= 1
        r = rate * bf if in_burst else rate / max(1.0, bf / 2)
        gaps.append(rng.exponential(1.0 / r))
    return np.cumsum(gaps)


def make_requests(t: dict, seed: int, vocab: int, n: int) -> List[Req]:
    """Open-loop stream of `n` requests. Sizes, tenants and gaps come
    from `sizes_seed`; `seed` makes every token."""
    fixed = _rng(t["sizes_seed"], 1)
    due = arrival_times(fixed, n, t)
    tenants = int(t["tenants"])
    w = 1.0 / np.arange(1, tenants + 1) ** float(t.get("tenant_zipf", 1.1))
    tenant = fixed.choice(tenants, size=n, p=w / w.sum())
    tail = _lengths(fixed, n, t["tail"])
    out = _lengths(fixed, n, t["output"])
    var = _rng(seed, 2)
    prefixes = [var.integers(1, vocab, size=int(t["prefix_tokens"])).tolist()
                for _ in range(tenants)]
    reqs = []
    for i in range(n):
        prompt = prefixes[int(tenant[i])] + var.integers(
            1, vocab, size=int(tail[i])).tolist()
        reqs.append(Req(index=i, due_s=float(due[i]), prompt=prompt,
                        max_new=int(out[i]), tenant=int(tenant[i])))
    return reqs


def _document_lengths(t: dict, n_docs: int, asks: int):
    """The fixed lengths of `n_docs` documents, their questions and
    answers, drawn in blocks of the file's `length_block` documents
    (all at once without the key): block 0 by the generator the whole
    list had before it was drawn in blocks, block b by one of its own.
    So a list made longer keeps every earlier request as it was, and a
    cell's numbers cannot move until a tree serves past the old end."""
    block = int(t.get("length_block", n_docs))
    parts = []
    for b, d0 in enumerate(range(0, n_docs, block)):
        n = min(block, n_docs - d0)
        fixed = _rng(t["sizes_seed"], 3, *((b,) if b else ()))
        parts.append((_lengths(fixed, n, t["document"]),
                      _lengths(fixed, n * asks, t["question"]
                               ).reshape(n, asks),
                      _lengths(fixed, n * asks, t["output"]
                               ).reshape(n, asks)))
    return tuple(np.concatenate(p) for p in zip(*parts))


def make_document_asks(t: dict, seed: int, vocab: int, n_docs: int
                       ) -> List[Req]:
    """Closed-loop list: documents in groups of `reuse_distance + 1`;
    within a group every document is asked once, then every document
    again, `asks_per_document` times — so exactly `reuse_distance`
    other requests lie between two asks of one document."""
    group = int(t["reuse_distance"]) + 1
    asks = int(t["asks_per_document"])
    doc_len, q_len, out = _document_lengths(t, n_docs, asks)
    var = _rng(seed, 4)
    reqs = []
    for g0 in range(0, n_docs, group):
        ids = list(range(g0, min(g0 + group, n_docs)))
        docs = {d: var.integers(1, vocab, size=int(doc_len[d])).tolist()
                for d in ids}
        for a in range(asks):
            for d in ids:
                prompt = docs[d] + var.integers(
                    1, vocab, size=int(q_len[d, a])).tolist()
                reqs.append(Req(index=len(reqs), due_s=None, prompt=prompt,
                                max_new=int(out[d, a]), doc=d, ask=a))
    return reqs


def token_batch(seed: int, step: int, batch: int, seq: int, vocab: int):
    """Batch `step` of the training stream: fresh tokens every step,
    `label = roll(tokens, -1)` along the sequence."""
    rng = _rng(seed, 1000 + step)
    tokens = rng.integers(1, vocab, size=(batch, seq), dtype=np.int32)
    return {"tokens": tokens,
            "positions": np.tile(np.arange(seq, dtype=np.int32), (batch, 1)),
            "label": np.roll(tokens, -1, axis=1)}
