"""The comparisons that decide `correct`, against lib/reference.py.

All of them run outside the timed window. Tolerances are numbers in the
configuration file (`check`), each with its reason in `check_why`
there: set from the error measured on the chip over several seeds, and
tight enough that the faults named in PERF.md would fail.
"""

from __future__ import annotations

import functools
import time

import numpy as np

from . import reference

SEQ_BUCKETS = (512, 1024, 2048)


def _bucket(n: int, positions: int) -> int:
    """The padded length a sequence of n tokens is compared at (a few
    lengths, so a few compiles), never past the position table."""
    for b in SEQ_BUCKETS:
        if n <= b <= positions:
            return b
    if n <= positions:
        return positions
    raise ValueError(f"sequence of {n} tokens is over {positions} positions")


# ------------------------------------------------------------- serving
def pick_requests(records: list, budget: int, seed: int, n: int) -> list:
    """A seeded sample of completed requests that always holds the
    shortest, the longest, one that hit the prefix cache and one whose
    prompt was prefilled in more than one chunk, where the run has them."""
    done = [r for r in records if r["done"] and r["tokens"]]
    if not done:
        return []
    by_len = sorted(done, key=lambda r: len(r["prompt"]))
    picks = [by_len[0], by_len[-1]]
    hit = [r for r in done if r["hit_tokens"] > 0]
    chunked = [r for r in done if len(r["prompt"]) - r["hit_tokens"] > budget]
    rng = np.random.default_rng([int(seed), 77])
    for group in (hit, chunked):
        if group:
            picks.append(group[int(rng.integers(len(group)))])
    rest = [r for r in done if all(r is not p for p in picks)]
    rng.shuffle(rest)
    return (picks + rest)[:max(n, len(picks))]


def check_serving(params, num_layers: int, positions: int, picks: list,
                  max_new: int) -> dict:
    """Teacher-force the reference on prompt + the engine's tokens. At
    every generated position: the reference's largest logit minus its
    logit of the token the engine chose (0 where they agree)."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(functools.partial(reference.logits_at,
                                   num_layers=num_layers))
    gaps, agree, total, rows_out = [], 0, 0, []
    for r in picks:
        seq = list(r["prompt"]) + list(r["tokens"])
        n_p, n_g = len(r["prompt"]), len(r["tokens"])
        toks = np.zeros((1, _bucket(len(seq), positions)), np.int32)
        toks[0, :len(seq)] = seq
        rows = np.zeros((max_new,), np.int32)
        rows[:n_g] = np.arange(n_p - 1, n_p - 1 + n_g)
        logits = np.asarray(fn(params, jnp.asarray(toks),
                               jnp.asarray(rows)))[:n_g]
        chosen = logits[np.arange(n_g), np.asarray(r["tokens"])]
        gap = logits.max(axis=1) - chosen
        gaps.append(float(gap.max()))
        agree += int((gap == 0).sum())
        total += n_g
        rows_out.append({"prompt": n_p, "new": n_g,
                         "hit_tokens": r["hit_tokens"],
                         "worst_gap": float(gap.max()),
                         "logit_std": float(logits.std())})
    return {"worst_gap": max(gaps) if gaps else None,
            "argmax_agree": agree, "positions": total, "requests": rows_out}


# ------------------------------------------------------------ training
def _sample_index(params, tokens, seed: int, k: int) -> dict:
    """Flat indices into every parameter tensor: `k` seeded entries (all
    of a smaller tensor). Half of the token table's lie in rows the
    batch touches, or nearly all of its sample would be zeros."""
    rng = np.random.default_rng([int(seed), 91])
    index = {}
    for op, ws in params.items():
        index[op] = {}
        for w, arr in ws.items():
            size = int(np.prod(arr.shape))
            idx = rng.integers(0, size, size=min(k, size))
            if op == "tok_embed":
                width = arr.shape[-1]
                rows = rng.choice(np.unique(tokens), size=len(idx) // 2)
                idx[:len(rows)] = rows * width + rng.integers(
                    0, width, size=len(rows))
            index[op][w] = np.sort(idx).astype(np.int32)
    return index


def _take(tree, index):
    import jax.numpy as jnp
    return {op: {w: jnp.take(tree[op][w].reshape(-1), idx)
                 for w, idx in ws.items()} for op, ws in index.items()}


def check_first_step(lm, conf: dict, batch: dict, seed: int) -> dict:
    """Reference first, then the system's first step (which is also the
    step that compiles): loss, logits at sampled positions, and the
    gradient the optimizer received, recovered from what the step
    leaves: Adam's first-moment slots over (1 - beta1)."""
    import jax
    import jax.numpy as jnp
    layers = conf["num_hidden_layers"]
    opt = conf["train"]["optimizer"]
    k = int(conf["check"]["grad_samples_per_tensor"])
    t0 = time.perf_counter()
    tokens, labels = batch["tokens"], batch["label"]
    index = _sample_index(lm.state.params, tokens, seed, k)
    rng = np.random.default_rng([int(seed), 92])
    rows = np.sort(rng.integers(0, tokens.size,
                                size=int(conf["check"]["logit_positions"])))
    ref_fn = jax.jit(functools.partial(
        reference.loss_logits_grad_samples, num_layers=layers))
    r_loss, r_logits, r_grads = jax.device_get(ref_fn(
        lm.state.params, jnp.asarray(tokens), jnp.asarray(labels),
        jnp.asarray(rows.astype(np.int32)), index))
    ref_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    s_logits = np.asarray(jnp.take(
        lm.forward(batch).reshape(-1, conf["vocab_size"]),
        jnp.asarray(rows.astype(np.int32)), axis=0), np.float32)
    take = jax.jit(_take)
    s_loss = float(lm.train_batch(batch)["loss"])
    first_step_s = time.perf_counter() - t0
    got = jax.device_get(take(lm.state.opt_state["m"], index))
    scale = 1.0 / (1.0 - opt["beta1"])
    s_grads = {o: {w: np.asarray(a) * scale for w, a in ws.items()}
               for o, ws in got.items()}
    per_tensor = {}
    for o, ws in r_grads.items():
        for w, ref in ws.items():
            ref = np.asarray(ref, np.float64)
            got = np.asarray(s_grads[o][w], np.float64)
            den = np.linalg.norm(ref)
            per_tensor[f"{o}/{w}"] = {
                "rel_err": float(np.linalg.norm(got - ref) / den)
                if den > 0 else float(np.linalg.norm(got)),
                "norm_ratio": float(np.linalg.norm(got) / den)
                if den > 0 else None}
    worst = max(per_tensor, key=lambda n: per_tensor[n]["rel_err"])
    return {"loss_sys": s_loss, "loss_ref": float(r_loss),
            "loss_abs_err": abs(s_loss - float(r_loss)),
            "logit_max_abs_err": float(np.max(np.abs(
                s_logits - np.asarray(r_logits)))),
            "logit_std": float(np.std(np.asarray(r_logits))),
            "grad_worst_tensor": worst,
            "grad_worst_rel_err": per_tensor[worst]["rel_err"],
            "grad_rel_err": {n: round(v["rel_err"], 5)
                             for n, v in per_tensor.items()},
            "tensors": len(per_tensor),
            "reference_s": ref_s, "first_step_s": first_step_s}


def verdict_first_step(found: dict, tol: dict) -> list:
    """The reasons this first step is not correct (empty = correct)."""
    why = []
    if not np.isfinite(found["loss_sys"]):
        why.append("first loss is not finite")
    if found["loss_abs_err"] > tol["loss_abs"]:
        why.append(f"loss differs by {found['loss_abs_err']:.4g} "
                   f"(> {tol['loss_abs']})")
    if found["logit_max_abs_err"] > tol["logit_abs"]:
        why.append(f"logits differ by {found['logit_max_abs_err']:.4g} "
                   f"(> {tol['logit_abs']})")
    bad = {n: e for n, e in found["grad_rel_err"].items()
           if not e <= tol["grad_rel"]}
    if bad:
        why.append(f"gradient differs in {len(bad)} tensors, worst "
                   f"{found['grad_worst_tensor']} by "
                   f"{found['grad_worst_rel_err']:.4g} (> {tol['grad_rel']})")
    return why
