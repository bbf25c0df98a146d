"""The serving attention kernel and the engine, COMPILED on-chip.

The CPU suite only ever runs the jnp path and the interpreted kernel;
here `paged_attention_ragged_v2` goes through Mosaic at head sizes 64
and 128 for every page format (f32, bf16, int8, fp8) and is compared
with its jnp twin, and a ServeEngine generates end to end with the
kernel the engine itself resolved.

Tolerances. With f32 q or f32 pages the kernel's operands are f32 at
HIGHEST MXU precision; with bf16 q and narrower pages they are exact
in bf16 and the probabilities go in as two bf16 halves, summed in f32:
its result is as accurate as the output dtype for every format. The jnp twin
is only that accurate when XLA is told so: on a TPU the default
precision of an f32 dot is one bf16 pass, hence the
`default_matmul_precision("highest")` around every reference. With
both sides f32-accurate what is left is summation order (2e-5, the
pre-v2 bound). A bf16 OUTPUT adds its own rounding: half a unit in the
8th bit of values of magnitude up to ~4, hence 2e-2.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from flexflow_tpu.kernels.paged_ragged_v2 import (PALLAS, _ragged_jnp,
                                                  build_work_list,
                                                  max_work_items,
                                                  paged_attention_ragged_v2,
                                                  quantize_kv_rows)

PAGE = 16


def _setup(seed, h, d, page_dtype, q_dtype, batch=4, pages_per_seq=8):
    """A few sequences of ragged length, several lanes per sequence at
    ragged positions (the mixed step's shape: chunk tokens + tails)."""
    rng = np.random.RandomState(seed)
    num_pages = 1 + batch * pages_per_seq
    lens = rng.randint(1, pages_per_seq * PAGE + 1, size=batch)
    kp = rng.randn(num_pages, PAGE, h, d).astype(np.float32)
    vp = rng.randn(num_pages, PAGE, h, d).astype(np.float32)
    table = np.zeros((batch, pages_per_seq), np.int32)
    pool = list(rng.permutation(np.arange(1, num_pages)))
    slots, poss = [], []
    for s, L in enumerate(lens):
        for i in range(-(-int(L) // PAGE)):
            table[s, i] = int(pool.pop())
        for p in sorted({int(L) - 1,
                         *(int(x) for x in rng.randint(0, int(L), 5))}):
            slots.append(s)
            poss.append(p)
    q = jnp.asarray(rng.randn(len(poss), h, d), q_dtype)
    kp, vp = jnp.asarray(kp), jnp.asarray(vp)
    scales = {}
    if jnp.dtype(page_dtype).itemsize == 1:
        kp, ks = quantize_kv_rows(kp, page_dtype)
        vp, vs = quantize_kv_rows(vp, page_dtype)
        scales = {"k_scales": ks, "v_scales": vs}
    else:
        kp, vp = kp.astype(page_dtype), vp.astype(page_dtype)
    return (q, kp, vp, jnp.asarray(table),
            jnp.asarray(np.asarray(slots, np.int32)),
            jnp.asarray(np.asarray(poss, np.int32) + 1)), scales


@pytest.mark.parametrize("h,d", [(32, 64), (16, 128)])
@pytest.mark.parametrize("page_dtype,q_dtype,tol", [
    (jnp.float32, jnp.float32, 2e-5),
    (jnp.bfloat16, jnp.bfloat16, 2e-2),
    (jnp.int8, jnp.float32, 2e-5),
    (jnp.float8_e4m3fn, jnp.float32, 2e-5),
])
@pytest.mark.parametrize("block_kv", [None, 4 * PAGE])
def test_ragged_v2_mosaic_matches_jnp(h, d, page_dtype, q_dtype, tol,
                                      block_kv):
    args, scales = _setup(h + d, h, d, page_dtype, q_dtype)
    out = jax.jit(lambda *a: paged_attention_ragged_v2(
        *a, use_pallas=True, block_kv=block_kv, **scales))(*args)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda *a: _ragged_jnp(
            *a, d ** -0.5, **scales))(*args)
    assert out.dtype == ref.dtype == jnp.dtype(q_dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("page_dtype,q_dtype,tol", [
    (jnp.float32, jnp.float32, 2e-5),
    (jnp.bfloat16, jnp.bfloat16, 2e-2),
    (jnp.int8, jnp.bfloat16, 2e-2),
])
def test_ragged_v2_mosaic_mixed_step_matches_jnp(page_dtype, q_dtype, tol):
    """One mixed step at the serving cell's geometry — 576 lanes, 128
    table columns, 32 heads of 64: 40 decode lanes, a 300-lane chunk
    that ends at position 1,000 (tiles and kv-blocks crossed, its rows
    sharing their fetches), an inactive tail — on the grid bound the
    engine proves for 64 sequences. Checked on every 7th lane (the
    twin gathers each lane's whole table)."""
    lanes, pp, seqs, h, d, pages = 576, 128, 64, 32, 64, 769
    rng = np.random.RandomState(5)
    table = np.zeros((seqs, pp), np.int32)
    free = list(rng.permutation(np.arange(1, pages)))
    lens = [1000] + [int(n) for n in rng.randint(1, 200, size=40)]
    for s, n in enumerate(lens):
        for i in range(-(-n // PAGE)):
            table[s, i] = int(free.pop())
    slots = np.zeros(lanes, np.int32)
    vis = np.ones(lanes, np.int32)
    slots[:40], vis[:40] = np.arange(1, 41), lens[1:]
    vis[40:340] = np.arange(701, 1001)              # slot 0's chunk
    kp = rng.randn(pages, PAGE, h, d).astype(np.float32)
    vp = rng.randn(pages, PAGE, h, d).astype(np.float32)
    q = jnp.asarray(rng.randn(lanes, h, d), q_dtype)
    scales = {}
    if jnp.dtype(page_dtype).itemsize == 1:
        kp, ks = quantize_kv_rows(jnp.asarray(kp), page_dtype)
        vp, vs = quantize_kv_rows(jnp.asarray(vp), page_dtype)
        scales = {"k_scales": ks, "v_scales": vs}
    else:
        kp, vp = jnp.asarray(kp, page_dtype), jnp.asarray(vp, page_dtype)
    table, slots, vis = (jnp.asarray(x) for x in (table, slots, vis))

    def call(q, kp, vp, t, s, n, **sc):
        work = build_work_list(
            t, s, n, page_size=PAGE, block_pages=8,
            max_items=max_work_items(lanes, pp, 8, slot_changes=seqs))
        return paged_attention_ragged_v2(q, kp, vp, t, s, n, work=work,
                                         use_pallas=True, **sc)

    out = np.asarray(jax.jit(call)(q, kp, vp, table, slots, vis, **scales),
                     np.float32)
    assert np.isfinite(out).all()                   # the inactive rows too
    sub = np.arange(0, lanes, 7)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda q, s, n: _ragged_jnp(
            q, kp, vp, table, s, n, d ** -0.5, **scales))(
            q[sub], slots[sub], vis[sub])
    np.testing.assert_allclose(out[sub], np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("kv_dtype", ["float32", "bfloat16", "int8",
                                      "float8_e4m3"])
def test_engine_end_to_end_on_the_compiled_kernel(kv_dtype):
    """generate() through the Mosaic kernel: every request finishes in
    range, pool invariants hold, nothing compiles after warm-up, the
    engine REPORTS the compiled kernel — and, with every matmul at full
    f32 precision so that only summation order separates the two
    engines, the greedy tokens equal the jnp engine's on exact (f32)
    pages."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import build_transformer_lm
    from flexflow_tpu.serve import ServeEngine

    cfg = FFConfig(batch_size=1, kv_page_size=16, kv_num_pages=65,
                   serve_max_seqs=4, serve_prefill_budget=64,
                   kv_dtype=kv_dtype)
    ff = build_transformer_lm(cfg, vocab_size=128, max_seq_len=128,
                              hidden=256, num_heads=4, num_layers=2,
                              ff_dim=512)
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, 128, size=n))
               for n in (3, 17, 40, 90, 17, 5)]   # 90 > budget: chunks
    with jax.default_matmul_precision("highest"):
        eng = ServeEngine(ff)                     # auto: Pallas on tpu
        assert eng.attn_impl == PALLAS
        warm = dict(eng.warmup())
        out = eng.generate(prompts, 8)
        assert eng.compile_counts() == warm
        assert eng.last_stats["attn_impl"] == PALLAS
        eng.cache.check_invariants()
        assert all(len(o) == 8 and all(0 <= t < 128 for t in o)
                   for o in out)
        if kv_dtype == "float32":
            ref = ServeEngine(ff, use_pallas=False).generate(prompts, 8)
            assert out == ref
