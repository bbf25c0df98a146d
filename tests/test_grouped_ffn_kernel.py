"""The gated expert as one Pallas kernel (kernels/grouped_ffn.py, PR 37),
through the Pallas interpreter at widths that still tile (D 256, F 256,
8 experts): against its twin `ops/moe.py::ragged_ffn` (three
`jax.lax.ragged_dot`) over the loads a serving step holds; its visit
list; where `grouped_ffn` takes it and where the twin; its gradient (the
twin's); and the engine built on the interpreted kernel against the
reference, with the record saying which expert ran.

Tolerance: both take bf16 operands and accumulate in f32; the twin
rounds `g`, `u` and `h` to bf16, the kernel `h` alone, so they differ by
bf16 roundings of values of order 1: measured 0.016 at a largest output
of 3.4; 2 % of the largest output is the limit, a tenth of what a row
given to the wrong expert shows.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from lib import olmoe_cell  # noqa: E402

from flexflow_tpu.config import CompMode, FFConfig  # noqa: E402
from flexflow_tpu.kernels import grouped_ffn as K  # noqa: E402
from flexflow_tpu.models.olmoe import build_olmoe_lm  # noqa: E402
from flexflow_tpu.ops import moe  # noqa: E402
from flexflow_tpu.serve import ServeEngine  # noqa: E402

S, D, F, E = 512, 256, 256, 8
BF = jnp.bfloat16
# live rows an expert; the row tile is 128
LOADS = {
    "uniform": [64] * 8,
    # the cell's skew: the busiest expert 4.3 times the mean
    "skewed": [206, 31, 12, 77, 5, 40, 9, 4],
    "an_expert_with_no_row": [90, 0, 70, 0, 0, 130, 60, 11],
    "one_expert_holds_every_row": [0, 0, 0, 512, 0, 0, 0, 0],
    "a_boundary_inside_a_row_tile": [100, 100, 0, 0, 0, 0, 0, 0],
    "boundaries_on_the_tiles_edges": [128, 0, 256, 0, 0, 0, 128, 0],
    "dead_rows_behind": [17, 3, 0, 1, 0, 0, 0, 120],
    "a_decode_step": [2, 1, 0, 3, 1, 1, 0, 0],
    "all_rows_dead": [0] * 8,
}


def _operands(seed=0, s=S, d=D, f=F, e=E, dtype=BF):
    r = np.random.default_rng(seed)
    a = lambda shape, scale: jnp.asarray(
        r.standard_normal(shape) * scale, dtype)
    return (a((s, d), 1.0), a((e, d, f), d ** -0.5), a((e, d, f), d ** -0.5),
            a((e, f, d), f ** -0.5))


def _f32(x):
    return np.asarray(x, np.float32)


@pytest.mark.parametrize("load", sorted(LOADS))
def test_kernel_equals_its_twin(load):
    rows, wg, wu, wd = _operands(1)
    counts = jnp.asarray(LOADS[load], jnp.int32)
    live = int(counts.sum())
    want = _f32(moe.ragged_ffn(rows, counts, wg, wu, wd, "silu"))
    got = _f32(K.grouped_ffn(rows, counts, wg, wu, wd, "silu",
                             interpret=True))
    assert not got[live:].any()
    if live:
        scale = float(np.abs(want).max())
        assert scale > 1.0
        np.testing.assert_allclose(got, want, atol=0.02 * scale, rtol=0)
        # a row given to its neighbour's expert would show
        assert np.abs(got[:live]).min(axis=0).max() > 0


def test_kernel_is_no_further_from_f32_than_its_twin():
    rows, *ws = _operands(2)
    counts = jnp.asarray(LOADS["skewed"], jnp.int32)
    exact = _f32(moe.ragged_ffn(rows.astype(jnp.float32), counts,
                                *(w.astype(jnp.float32) for w in ws),
                                "silu"))
    twin = _f32(moe.ragged_ffn(rows, counts, *ws, "silu"))
    got = _f32(K.grouped_ffn(rows, counts, *ws, "silu", interpret=True))
    assert np.abs(got - exact).mean() <= np.abs(twin - exact).mean()


@pytest.mark.parametrize("load", ["skewed", "a_boundary_inside_a_row_tile"])
def test_two_tiles_of_f_equal_one(load):
    rows, *ws = _operands(3)
    counts = jnp.asarray(LOADS[load], jnp.int32)
    one = _f32(K.grouped_ffn(rows, counts, *ws, "silu", f_tile=256,
                             interpret=True))
    two = _f32(K.grouped_ffn(rows, counts, *ws, "silu", f_tile=128,
                             interpret=True))
    # the same products; the f32 accumulator takes them in two parts
    np.testing.assert_allclose(two, one, atol=0.02, rtol=0)
    assert K.choose_f_tile(256) == 256 and K.choose_f_tile(1024) == 1024
    assert K.choose_f_tile(1536) == 768


@pytest.mark.parametrize("row_tile", [32, 128])
def test_visit_list_holds_each_tile_s_experts_once_in_row_order(row_tile):
    counts = np.asarray(LOADS["an_expert_with_no_row"], np.int32)
    offsets, tiles, experts, n = (np.asarray(a) for a in K.visit_list(
        jnp.asarray(counts), S, row_tile))
    ends = np.cumsum(counts)
    np.testing.assert_array_equal(offsets, np.concatenate([[0], ends]))
    owner = np.searchsorted(ends, np.arange(ends[-1]), side="right")
    want = sorted({(int(r) // row_tile, int(e))
                   for r, e in enumerate(owner)})
    assert list(zip(tiles[:n].tolist(), experts[:n].tolist())) == want
    assert len(tiles) == S // row_tile + E
    # an expert without a row is never visited; the dead visits repeat
    # the last live one, so they fetch nothing
    assert not set(experts[:n].tolist()) & {1, 3, 4}
    assert (tiles[n:] == tiles[n - 1]).all()
    assert (experts[n:] == experts[n - 1]).all()


@pytest.mark.parametrize("case,rows_dtype,w_dtype,s,d,f,interpret,want", [
    ("bf16_tiled_interpreted", BF, BF, 512, 256, 256, True, True),
    ("the_cpu_compiles_no_kernel", BF, BF, 512, 256, 256, False, False),
    ("f32_rows", jnp.float32, BF, 512, 256, 256, True, False),
    ("f32_weights", BF, jnp.float32, 512, 256, 256, True, False),
    ("d_off_the_lanes", BF, BF, 512, 192, 256, True, False),
    ("f_off_the_lanes", BF, BF, 512, 256, 96, True, False),
    ("rows_off_the_row_tile", BF, BF, 72, 256, 256, True, False),
])
def test_supported_by_dtype_width_and_platform(case, rows_dtype, w_dtype, s,
                                               d, f, interpret, want):
    rows = jax.ShapeDtypeStruct((s, d), rows_dtype)
    wg = jax.ShapeDtypeStruct((E, d, f), w_dtype)
    assert K.supported(rows, wg, interpret=interpret) is want
    impl = moe.expert_impl(rows, wg, interpret=interpret)
    assert impl == ("pallas_interpret" if want else "ragged_dot")
    assert moe.expert_impl(rows, wg, use_pallas=False,
                           interpret=interpret) == "ragged_dot"


def test_on_a_tpu_backend_the_kernel_is_the_default(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows = jax.ShapeDtypeStruct((S, D), BF)
    assert moe.expert_impl(rows, jax.ShapeDtypeStruct((E, D, F), BF)) \
        == "pallas"


def test_grouped_ffn_takes_the_twin_where_the_kernel_does_not_apply():
    rows, *ws = _operands(4, s=72, d=64, f=32, dtype=jnp.float32)
    counts = jnp.asarray([9, 0, 20, 1, 0, 30, 2, 3], jnp.int32)
    np.testing.assert_array_equal(
        moe.grouped_ffn(rows, counts, *ws, "silu", interpret=True),
        moe.ragged_ffn(rows, counts, *ws, "silu"))


def test_gradient_through_grouped_ffn_is_the_twin_s():
    rows, *ws = _operands(5)
    counts = jnp.asarray(LOADS["an_expert_with_no_row"], jnp.int32)
    dy = jnp.asarray(np.random.default_rng(6).standard_normal((S, D)),
                     jnp.float32)

    def loss(fn):
        return lambda r, *w: jnp.sum(
            fn(r, counts, *w, "silu").astype(jnp.float32) * dy)

    fused = lambda *a: moe.grouped_ffn(*a, interpret=True)
    got = jax.grad(loss(fused), argnums=(0, 1, 2, 3))(rows, *ws)
    want = jax.grad(loss(moe.ragged_ffn), argnums=(0, 1, 2, 3))(rows, *ws)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and float(jnp.abs(w).max()) > 0
        np.testing.assert_array_equal(_f32(g), _f32(w))
    # and the forward under differentiation is the kernel's
    y, _ = jax.vjp(lambda r: fused(r, counts, *ws, "silu"), rows)
    np.testing.assert_array_equal(
        _f32(y), _f32(K.grouped_ffn(rows, counts, *ws, "silu",
                                    interpret=True)))


# ------------------------------- the engine on the interpreted kernel
VOCAB, HIDDEN, HEADS, LAYERS, TOPK, WIDTH = 128, 128, 4, 2, 2, 128
CONF = {"vocab_size": VOCAB, "hidden_size": HIDDEN,
        "num_attention_heads": HEADS, "num_hidden_layers": LAYERS,
        "num_experts": E, "num_experts_per_tok": TOPK,
        "intermediate_size": WIDTH, "max_position_embeddings": 256,
        "rope_theta": 10000, "rms_norm_eps": 1e-5,
        "norm_topk_prob": False}


def _engine(budget=60, **kwargs):
    """60 + 4 lanes of 2 slots: one row tile."""
    cfg = FFConfig(batch_size=1, seed=5, kv_page_size=16, kv_num_pages=65,
                   serve_max_seqs=4, serve_prefill_budget=budget,
                   serve_spec_decode=False, compute_dtype="bfloat16",
                   param_dtype="bfloat16", kv_dtype="bfloat16")
    lm = build_olmoe_lm(cfg, vocab_size=VOCAB, max_seq_len=256,
                        hidden=HIDDEN, num_heads=HEADS, num_layers=LAYERS,
                        num_experts=E, experts_per_token=TOPK,
                        expert_dim=WIDTH)
    lm.compile(comp_mode=CompMode.INFERENCE)
    return ServeEngine(lm, **kwargs)


@pytest.fixture(scope="module")
def engine():
    eng = _engine(interpret=True)
    eng.warmup()
    yield eng
    eng.close()


def test_engine_record_says_which_expert_ran(engine):
    assert engine.expert_impl == "pallas_interpret"
    assert engine.boot_stats["expert_impl"] == "pallas_interpret"
    assert engine._program_fingerprint()["expert_impl"] \
        == "pallas_interpret"


@pytest.mark.parametrize("budget,kwargs,want", [
    (60, {}, "ragged_dot"),                       # a CPU engine: the twin
    (60, {"use_pallas": False}, "ragged_dot"),
    (32, {"interpret": True}, "ragged_dot"),      # 72 rows: off the tile
])
def test_engine_takes_the_twin_where_the_kernel_does_not_apply(
        budget, kwargs, want):
    eng = _engine(budget, **kwargs)
    assert eng.expert_impl == want
    eng.close()


def test_an_engine_without_an_expert_layer_records_none():
    from flexflow_tpu.models.transformer import build_transformer_lm
    cfg = FFConfig(batch_size=1, kv_page_size=8, kv_num_pages=73,
                   serve_max_seqs=8, serve_prefill_budget=48)
    eng = ServeEngine(build_transformer_lm(
        cfg, vocab_size=89, max_seq_len=64, hidden=32, num_heads=4,
        num_layers=2, ff_dim=64), interpret=True)
    assert eng.expert_impl is None
    assert eng._program_fingerprint()["expert_impl"] is None
    eng.close()


def test_engine_on_the_interpreted_kernel_equals_the_reference(engine):
    """test_olmoe.py's bf16 case (one prompt of several chunks, then
    decode), the expert layer on the kernel: inside the served
    tolerance, and nothing dropped."""
    tokens = np.random.default_rng(15).integers(1, VOCAB, 75).tolist()
    rows, stats = olmoe_cell.logits_through_cache(engine, CONF, [tokens], 8)
    assert rows[0]["prefill_chunks"] >= 2 and rows[0]["new"] == 8
    assert rows[0]["logit_std"] > 0.3
    assert rows[0]["logit_abs_err"] <= 0.3, rows
    assert stats["experts"]["dropped"] == 0
    engine.generate([tokens[:9]], max_new_tokens=2)
    assert engine.last_stats["expert_impl"] == "pallas_interpret"


def test_mixed_step_on_the_kernel_lowers_no_grouped_matmul(engine):
    c = engine.cache_cfg
    z = np.zeros((engine.mixed_width,), np.int32)
    pts = np.zeros((c.max_seqs, c.pages_per_seq), np.int32)
    text = jax.jit(engine._mixed_impl).lower(
        engine._step_params, engine._device_pool(), z, z, z, z, pts, z,
        z + 1, z[:engine.head_rows], z - 1, z[:engine.head_rows]
    ).as_text(debug_info=True)
    assert "serve_step/layer1/experts/" in text
    assert "ragged_dot" not in text
