"""The fused expert kernel COMPILED on the chip (kernels/grouped_ffn.py,
PR 37) at OLMoE's served shape — 4608 expert-sorted rows of 2048, 64
experts of 1024, bf16, twelve layers a program, each with weights of its
own: parity with its twin (ops/moe.py::ragged_ffn, three
`jax.lax.ragged_dot`), and the go / no-go table — ms a layer of (a) the
twin, (b) a plain read of the layer's 805 MB of weights (what the chip
can stream), (c) `megablox.gmm` as shipped, three calls, (d) the kernel
over the candidate row tiles and tiles of F — with 320, 1,104 and 4,608
live slots drawn as a served step draws them (eight distinct experts a
token, the load's max over its mean about 4.3). Run with `-s` to see the
table; it is also written to chiprun_out/grouped_ffn_tpu.json.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels import grouped_ffn as K
from flexflow_tpu.ops import moe

S, D, E, F, TOPK, LAYERS = 4608, 2048, 64, 1024, 8, 12
LIVE = (320, 1104, 4608)            # 40, 138 and 576 live lanes
ROW_TILES = (32, 64, 128)
F_TILES = (256, 512, 1024)
HBM_GBS = 819.0                     # one v5e chip (Google Cloud, "TPU v5e")
EXPERT_BYTES = 3 * D * F * 2
BF = jnp.bfloat16
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _counts(live_slots: int, seed: int = 0) -> np.ndarray:
    """Each of live_slots / 8 tokens takes 8 distinct experts, by
    Gumbel top-k over a log-normal popularity (sigma 0.75: over 64
    experts the busiest holds about 4.3 times the mean)."""
    r = np.random.default_rng(seed)
    logp = 0.75 * r.standard_normal(E)
    g = logp + r.gumbel(size=(live_slots // TOPK, E))
    picks = np.argsort(-g, axis=1)[:, :TOPK]
    return np.bincount(picks.ravel(), minlength=E).astype(np.int32)


def _weights(layer: int):
    ks = jax.random.split(jax.random.key(layer), 3)
    return (jax.random.normal(ks[0], (E, D, F), BF) * D ** -0.5,
            jax.random.normal(ks[1], (E, D, F), BF) * D ** -0.5,
            jax.random.normal(ks[2], (E, F, D), BF) * F ** -0.5)


def _rows(seed=0):
    return jax.random.normal(jax.random.key(100 + seed), (S, D), BF)


@pytest.mark.parametrize("live", LIVE)
def test_kernel_compiled_matches_its_twin(live):
    rows, counts = _rows(live), jnp.asarray(_counts(live, seed=live))
    ws = _weights(0)
    want = jax.jit(lambda *a: moe.ragged_ffn(*a, "silu"))(rows, counts, *ws)
    got = jax.jit(lambda *a: moe.grouped_ffn(*a, "silu"))(rows, counts, *ws)
    assert moe.expert_impl(rows, ws[0]) == "pallas"
    want, got = (np.asarray(a, np.float32) for a in (want, got))
    assert not got[live:].any()
    # the twin rounds g, u and h to bf16, the kernel h alone
    scale = float(np.abs(want).max())
    assert scale > 0.5
    np.testing.assert_allclose(got, want, atol=0.02 * scale, rtol=0)
    # and against the products in f32 the kernel is the nearer
    exact = np.asarray(jax.jit(lambda *a: moe.ragged_ffn(*a, "silu"))(
        rows.astype(jnp.float32), counts,
        *(w.astype(jnp.float32) for w in ws)))
    assert np.abs(got - exact).mean() <= np.abs(want - exact).mean()


def test_engine_on_the_chip_takes_the_kernel_and_says_so():
    """A small bf16 OLMoE (hidden 256, 8 experts of 256, 64 lanes of 2
    slots: one row tile) served on the compiled kernel: the record says
    "pallas", and prefill + decode through the cache stay inside the
    served tolerance of the f32 reference."""
    import sys
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from lib import olmoe_cell

    from flexflow_tpu.config import CompMode, FFConfig
    from flexflow_tpu.models.olmoe import build_olmoe_lm
    from flexflow_tpu.serve import ServeEngine
    conf = {"vocab_size": 128, "hidden_size": 256, "num_attention_heads": 2,
            "num_hidden_layers": 2, "num_experts": 8,
            "num_experts_per_tok": 2, "intermediate_size": 256,
            "max_position_embeddings": 256, "rope_theta": 10000,
            "rms_norm_eps": 1e-5, "norm_topk_prob": False}
    cfg = FFConfig(batch_size=1, seed=5, kv_page_size=16, kv_num_pages=65,
                   serve_max_seqs=4, serve_prefill_budget=60,
                   serve_spec_decode=False, compute_dtype="bfloat16",
                   param_dtype="bfloat16", kv_dtype="bfloat16")
    lm = build_olmoe_lm(cfg, vocab_size=128, max_seq_len=256, hidden=256,
                        num_heads=2, num_layers=2, num_experts=8,
                        experts_per_token=2, expert_dim=256)
    lm.compile(comp_mode=CompMode.INFERENCE)
    eng = ServeEngine(lm)
    eng.warmup()
    assert eng.expert_impl == eng.boot_stats["expert_impl"] == "pallas"
    tokens = np.random.default_rng(15).integers(1, 128, 75).tolist()
    rows, stats = olmoe_cell.logits_through_cache(eng, conf, [tokens], 8)
    assert rows[0]["logit_std"] > 0.3
    assert rows[0]["logit_abs_err"] <= 0.3, rows
    assert stats["experts"]["dropped"] == 0
    eng.close()


def _ms_a_layer(fn, rows, weights, counts_by_live, reps=5):
    """All twelve layers in one program, `reps` calls: ms a layer for
    each load."""
    def twelve(rows, counts, weights):
        ys = jnp.float32(0)
        for ws in weights:
            ys = ys + fn(rows, counts, *ws)[0, 0].astype(jnp.float32)
        return ys

    twelve = jax.jit(twelve)
    out = {}
    for live, counts in counts_by_live.items():
        jax.block_until_ready(twelve(rows, counts, weights))
        t0 = time.perf_counter()
        for _ in range(reps):
            ys = twelve(rows, counts, weights)
        jax.block_until_ready(ys)
        out[live] = (time.perf_counter() - t0) / reps / LAYERS * 1e3
    return out


def test_a_layer_s_expert_time_by_implementation_and_load():
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    rows = _rows()
    weights = [_weights(layer) for layer in range(LAYERS)]
    counts = {live: jnp.asarray(_counts(live)) for live in LIVE}
    touched = {live: int((np.asarray(c) > 0).sum())
               for live, c in counts.items()}
    table = {"device": jax.devices()[0].device_kind,
             "shape": [S, D, E, F], "layers": LAYERS,
             "touched_experts": touched,
             "load_max_over_mean": {
                 live: float(np.asarray(c).max() / np.asarray(c).mean())
                 for live, c in counts.items()}}

    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)

    def measure(name, fn):
        try:
            row = _ms_a_layer(fn, rows, weights, counts)
            print(f"{name}: " + ", ".join(
                f"live {k} {v:.3f} ms" for k, v in row.items()))
        except Exception as e:      # a candidate the compiler refuses
            row = {"error": f"{type(e).__name__}: {e}"[:400]}
            print(f"{name}: {row['error']}")
        table[name] = row
        with open(os.path.join(out, "grouped_ffn_tpu.json"), "w") as f:
            json.dump(table, f, indent=1)

    measure("ragged_dot_x3",
            lambda r, c, *w: moe.ragged_ffn(r, c, *w, "silu"))

    def read(r, c, wg, wu, wd):
        return sum(jnp.max(w).astype(jnp.float32)
                   for w in (wg, wu, wd)).reshape(1, 1)
    measure("plain_read_805MB", read)

    for tiling in ((128, 128, 128), (128, 1024, 1024)):
        def megablox(r, c, wg, wu, wd, tiling=tiling):
            mm = lambda a, w: gmm(a, w, c, jnp.float32, tiling).astype(BF)
            return mm(jax.nn.silu(mm(r, wg)) * mm(r, wu), wd)
        measure("megablox_gmm_x3_" + "x".join(map(str, tiling)), megablox)

    for row_tile in ROW_TILES:
        for f_tile in F_TILES:
            def fused(r, c, *w, row_tile=row_tile, f_tile=f_tile):
                return K.grouped_ffn(r, c, *w, "silu", row_tile=row_tile,
                                     f_tile=f_tile)
            measure(f"fused_rows{row_tile}_f{f_tile}", fused)

    chosen = f"fused_rows{K.ROW_TILE}_f{K.choose_f_tile(F)}"
    table["chosen"] = chosen
    table["chosen_hbm_share"] = {
        live: touched[live] * EXPERT_BYTES / (ms * 1e-3) / (HBM_GBS * 1e9)
        for live, ms in table[chosen].items()}
    print("chosen", chosen, "share of 819 GB/s over the touched weights:",
          table["chosen_hbm_share"])
    with open(os.path.join(out, "grouped_ffn_tpu.json"), "w") as f:
        json.dump(table, f, indent=1)
    # the go / no-go of PR 37: a quarter under the three grouped matmuls
    # where a served step lives, and over half the chip's bandwidth
    for live in LIVE[:2]:
        assert table[chosen][live] < 0.75 * table["ragged_dot_x3"][live], \
            table
    assert table["chosen_hbm_share"][LIVE[1]] >= 0.55, table
