"""KVPool (serve/kv_cache.py): the one owner of the device pool's format.

  * write -> layer read-back, per storage format: what a lane stores is
    what the kernel's operands hold at its (page, offset), exactly on
    lossless pools and to `dequantize_kv`'s bound on quantized ones;
  * rows -> with_rows: the handoff's gather and scatter move whole
    pages between two pools bit for bit (scales included);
  * one program: an engine of each (quantised?) x (tensor_parallel=2?)
    kind compiles exactly one `mixed` program, and serves the tokens
    `generate_reference` gives on the f32 pool.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.kernels.paged_ragged_v2 import dequantize_kv
from flexflow_tpu.models.transformer import build_transformer_lm
from flexflow_tpu.serve import ServeEngine
from flexflow_tpu.serve.kv_cache import KVCacheConfig, KVPool

KV_DTYPES = ("float32", "bfloat16", "int8", "float8_e4m3")
# worst-case relative error of one stored element against its row's
# amax (tests/test_kv_quant.py's bounds): int8 rounds to amax/127
# steps (half a step), e4m3's 3-bit mantissa to 2^-4 relative
REL_ERR = {"float32": 0.0, "bfloat16": 2.0 ** -8, "int8": 0.5 / 127,
           "float8_e4m3": 2.0 ** -4}


def _cfg(kv_dtype):
    return KVCacheConfig(num_layers=3, num_heads=2, head_dim=8,
                         page_size=4, num_pages=9, max_seqs=2,
                         max_seq_len=16, kv_dtype=kv_dtype)


def _stored(pool, layer):
    """`layer`'s K and V as the kernel reads them, in f32."""
    k, v, ks, vs, _ = pool.layer(layer)
    if pool.quantized:
        return np.asarray(dequantize_kv(k, ks)), \
            np.asarray(dequantize_kv(v, vs))
    return np.asarray(k, np.float32), np.asarray(v, np.float32)


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_write_then_layer_reads_back(kv_dtype):
    cfg = _cfg(kv_dtype)
    pool = KVPool.alloc(cfg)
    assert pool.quantized == cfg.quantized
    assert len(jax.tree.leaves(pool)) == (4 if cfg.quantized else 2)
    rng = np.random.RandomState(0)
    pages = jnp.asarray([1, 1, 5, 8, 0], jnp.int32)   # last: the sink
    offs = jnp.asarray([0, 3, 2, 1, 0], jnp.int32)
    k = rng.randn(5, cfg.num_heads, cfg.head_dim).astype(np.float32)
    v = rng.randn(5, cfg.num_heads, cfg.head_dim).astype(np.float32)
    k[3] = 0.0     # an all-zero row stores scale 0 and reads back 0
    written = jax.jit(KVPool.write, static_argnums=(1,))(
        pool, 1, pages, offs, jnp.asarray(k), jnp.asarray(v))
    written.check_geometry(cfg)
    got_k, got_v = _stored(written, 1)
    for want, got in ((k, got_k), (v, got_v)):
        rows = got[np.asarray(pages), np.asarray(offs)]
        bound = REL_ERR[kv_dtype] * np.abs(want).max(-1, keepdims=True)
        assert np.all(np.abs(rows - want) <= bound * 1.0001)
    assert np.all(got_k[8, 1] == 0.0)
    # the other layers, and every row not addressed, are untouched
    for layer in (0, 2):
        assert not np.any(_stored(written, layer)[0])
    untouched = np.ones(got_k.shape[:2], bool)
    untouched[np.asarray(pages), np.asarray(offs)] = False
    assert not np.any(got_k[untouched]) and not np.any(got_v[untouched])
    written.check_scales([("row", int(p), int(o))
                          for p, o in zip(pages, offs)])


@pytest.mark.parametrize("kv_dtype", KV_DTYPES)
def test_rows_with_rows_round_trip(kv_dtype):
    cfg = _cfg(kv_dtype)
    rng = np.random.RandomState(1)
    n = cfg.num_pages * cfg.page_size
    pages = jnp.asarray(np.repeat(np.arange(cfg.num_pages), cfg.page_size),
                        jnp.int32)
    offs = jnp.asarray(np.tile(np.arange(cfg.page_size), cfg.num_pages),
                       jnp.int32)
    src = KVPool.alloc(cfg)
    for layer in range(cfg.num_layers):
        src = src.write(
            layer, pages, offs,
            jnp.asarray(rng.randn(n, cfg.num_heads, cfg.head_dim),
                        jnp.float32),
            jnp.asarray(rng.randn(n, cfg.num_heads, cfg.head_dim),
                        jnp.float32))
    take = jnp.asarray([7, 2, 4], jnp.int32)
    put = jnp.asarray([1, 3, 8], jnp.int32)
    rows = src.rows(take)
    assert jax.tree.structure(rows) == jax.tree.structure(src)
    # across the wire: host numpy leaves, as a shipment carries them
    wire = jax.tree.map(np.asarray, rows)
    dst = KVPool.alloc(cfg).with_rows(put, wire)
    dst.check_geometry(cfg)
    for a, b in zip(jax.tree.leaves(src), jax.tree.leaves(dst)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.array_equal(a[:, np.asarray(take)],
                              b[:, np.asarray(put)])
        rest = np.setdiff1d(np.arange(cfg.num_pages), np.asarray(put))
        assert not np.any(b[:, rest])


def test_specs_name_the_head_axis_of_every_leaf():
    pool = KVPool.alloc(_cfg("int8"))
    for leaf, spec in zip(jax.tree.leaves(pool),
                          jax.tree.leaves(KVPool.specs("tensor"))):
        assert len(spec) == leaf.ndim
        assert [i for i, a in enumerate(spec) if a == "tensor"] == [3]
        assert leaf.shape[3] == 2       # num_heads


@pytest.mark.parametrize("tp", [None, 2], ids=["1dev", "tp2"])
@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_every_engine_kind_compiles_one_mixed_program(kv_dtype, tp):
    """(quantised?) x (mesh?): four engines, one step function. Each
    compiles exactly one `mixed` program; the f32 ones serve the
    reference's tokens exactly, the int8 ones to the quantised
    contract (assert_token_parity)."""
    rng = np.random.RandomState(3)
    prompts = [list(rng.randint(1, 61, size=rng.randint(4, 28)))
               for _ in range(4)]
    cfg = FFConfig(batch_size=1, kv_page_size=4, kv_num_pages=65,
                   kv_dtype=kv_dtype, serve_max_seqs=4,
                   serve_prefill_budget=32)
    # vocab 61 and ff_dim 72 do not divide by 2: the mesh pads them
    lm = build_transformer_lm(cfg, vocab_size=61, max_seq_len=64,
                              hidden=32, num_heads=4, num_layers=2,
                              ff_dim=72)
    eng = ServeEngine(lm, tensor_parallel=tp)
    counts = eng.warmup()
    assert counts["mixed"] == 1, counts
    assert "prefill" not in counts and "decode" not in counts
    assert eng.pool.quantized == (kv_dtype == "int8")
    ref = eng.generate_reference(prompts, 6)
    out = eng.generate(prompts, 6)
    assert eng.compile_counts()["mixed"] == 1
    if kv_dtype == "float32":
        assert out == ref
    else:
        eng.assert_token_parity(prompts, out, ref)
    eng.check_kv_scales()
    eng.cache.check_invariants(eng.pool)


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("packed", [False, True], ids=["unpacked", "packed"])
def test_a_packed_pool_s_leaves_reach_the_kernel_whole(packed, kv_dtype,
                                                       impl):
    """What `mixers._paged` does to the pool's leaves before the kernel
    has them. A packed pool: each leaf is reshaped (its layers' pages
    as rows) and nothing else — no slice of a layer that XLA would copy
    out for the call — and the layer reaches the kernel as a scalar, a
    fifth scalar-prefetch operand. An unpacked pool: a layer's slice of
    every leaf and the call of before, four scalar operands."""
    import dataclasses
    import types

    import jax.extend.core
    from flexflow_tpu.kernels.paged_ragged_v2 import JNP, build_work_list
    from flexflow_tpu.serve import mixers
    cfg = dataclasses.replace(_cfg(kv_dtype), packed_heads=packed)
    pool = KVPool.alloc(cfg)
    assert bool(pool.heads) == packed
    leaves = len(jax.tree.leaves(pool))
    t = 8
    tables = jnp.arange(cfg.max_seqs * cfg.pages_per_seq,
                        dtype=jnp.int32).reshape(cfg.max_seqs, -1) % 9
    slots = jnp.zeros(t, jnp.int32)
    lens = jnp.arange(1, t + 1, dtype=jnp.int32)
    q = jnp.ones((t, cfg.num_heads, cfg.head_dim), jnp.float32)
    g = types.SimpleNamespace(
        arch=types.SimpleNamespace(attn_scale=0.5), block_kv=4,
        attn_kw={"use_pallas": impl != JNP, "interpret": impl != JNP})
    lanes = types.SimpleNamespace(lane_slots=slots)
    work = None if impl == JNP else build_work_list(
        tables, slots, lens, page_size=cfg.page_size, block_pages=1)
    layer = 2
    jaxpr = jax.make_jaxpr(lambda pool: mixers._paged(
        g, q, pool, layer, tables, lanes, lens, work))(pool).jaxpr
    first = [e for e in jaxpr.eqns
             if any(v in jaxpr.invars for v in e.invars)]
    names = sorted(e.primitive.name for e in first)
    if packed:
        assert names == ["reshape"] * leaves
        p = cfg.num_pages
        assert {e.outvars[0].aval.shape for e in first} == {
            (3 * p, 4, 16)} | ({(3 * p, 4, 2)} if cfg.quantized else set())
    else:
        assert names == ["dynamic_slice"] * leaves \
            or names == ["slice"] * leaves
        assert all(e.outvars[0].aval.shape[:2] == (1, cfg.num_pages)
                   for e in first)
    if impl == JNP:
        return
    (call,) = [e for e in jaxpr.eqns
               if e.params.get("name") == "_ragged_v2_pallas"]
    (kernel,) = [e for e in call.params["jaxpr"].eqns
                 if e.primitive.name == "pallas_call"]
    assert kernel.params["grid_mapping"].num_index_operands == 4 + packed
    # the layer's first row: a scalar operand of the nested call, so
    # every layer of a leaf shares its trace
    scalars = [int(v.val) for v in call.invars
               if isinstance(v, jax.extend.core.Literal) and v.aval.shape == ()]
    assert scalars == ([layer * cfg.num_pages] if packed else [])
