"""The training flash kernels through the chip's OWN compiler, for a v5e
that is described and not attached (libtpu's compile-only topology): what
Mosaic refuses — a slice off the tiling, too much VMEM, a call GSPMD
cannot partition — shows here and costs no chip time. Nothing runs, so
nothing here says a result or a time (tests_tpu/test_flash_tpu.py does,
on the chip).

The serving kernels (the paged kernel at grouped heads, the scan of the
state-space layers) are compiled here too, at their served shapes.

The topology is described inside a fixture, never at import: one process
at a time may load the TPU's library, and the suite runs under several
workers that all import this file.
"""

import functools
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from flexflow_tpu.kernels import flash_attention as fa

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def as_tpu(monkeypatch):
    """The gate and the kernel's platform check ask the default backend,
    which is the CPU here: steer them, for the lowering alone. And the
    suite's conftest asks f32 products of every matmul (a CPU's golden
    values); the chip's program runs the default, bf16 operands as they
    are."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.default_matmul_precision("default"):
        yield


@pytest.mark.parametrize("b,s,h,d", [
    (1, 2048, 32, 64),     # pretrain-1chip: two heads a slab
    (2, 2048, 16, 128),    # one head a slab
    (1, 1024, 64, 32),     # four heads a slab
    (1, 1024, 3, 64),      # an odd head count, padded by one zero head
    (1, 8192, 8, 64),      # whole-sequence operands past Mosaic's 16 MiB
])
def test_three_kernels_compile_for_a_v5e(topo, as_tpu, b, s, h, d):
    one = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention_bshd(q, k, v, causal=True)
                       .astype(jnp.float32))
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    assert all(name in text for name in KERNELS)
    assert text.count("tpu_custom_call") >= 3


def test_sharded_attention_op_compiles_for_2x2(topo, as_tpu):
    """OPT's widths on a data 2 x model 2 mesh: the op makes its call
    per shard (GSPMD cannot partition a Mosaic call), and the auto gate
    reads the shard's shapes."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.op import OpContext

    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"))
    b, s, h, d = 2, 2048, 32, 64
    ff = FFModel(FFConfig())
    x = ff.create_tensor((b, s, h * d), dtype=jnp.bfloat16, name="x")
    ff.multihead_attention(x, x, x, h * d, h, causal=True, name="mha")
    op = ff.ops[0]
    ctx = OpContext(training=True, mesh=mesh,
                    op_strategy=types.SimpleNamespace(mesh_axis_for={
                        "sample": "data", "head": "model"}.get))

    def loss(params, x):
        return jnp.sum(op.forward(params, [x] * 3, ctx)[0]
                       .astype(jnp.float32))
    specs = {"wq": P(None, "model", None), "wk": P(None, "model", None),
             "wv": P(None, "model", None), "wo": P("model", None, None)}
    shaped = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16)
    params = {n: shaped(w.shape, sharding=NamedSharding(
        mesh, specs.get(n, P()))) for n, w in op.weight_specs().items()}
    xs = shaped((b, s, h * d), sharding=NamedSharding(
        mesh, P("data", None, None)))
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, xs).compile().as_text()
    assert op.attn_impl == "flash"
    assert all(name in text for name in KERNELS)


@pytest.mark.parametrize("window,t,slots,pp,bp,hq,h", [
    (0, 576, 64, 512, 13, 40, 10), (512, 576, 64, 512, 13, 40, 10),
    (0, 544, 32, 1280, 16, 128, 8), (4096, 544, 32, 1280, 16, 128, 8)])
def test_paged_kernel_compiles_at_grouped_heads_under_a_window(
        topo, as_tpu, window, t, slots, pp, bp, hq, h):
    """The serving kernel at Phi-4-mini-flash's served geometry (PR 32):
    40 query heads over 10 key/value heads of 128 (group 4), bf16 pages
    of 16, 576 lanes, 8192 positions — with the window layers' list and
    mask, and without; and at Command A+'s (PR 43): 128 query heads
    over 8 key/value heads of 128 (group 16: 512 rows a head in the
    whole-tile product), 544 lanes, 20480 positions, a window of
    4096."""
    from flexflow_tpu.kernels import paged_ragged_v2 as pr
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    items = pr.max_work_items(
        t, pp, bp, slot_changes=slots,
        window_blocks=pr.window_block_bound(window, bp * 16)
        if window else 0)

    def call(q, kp, vp, pt, lane_slots, lens):
        work = pr.build_work_list(pt, lane_slots, lens, page_size=16,
                                  block_pages=bp, max_items=items,
                                  window=window)
        return pr.paged_attention_ragged_v2(
            q, kp, vp, pt, lane_slots, lens, scale=0.125, work=work,
            use_pallas=True, window=window)

    pages = sds((4161, 16, h, 128), jnp.bfloat16)
    text = jax.jit(call).lower(
        sds((t, hq, 128), jnp.bfloat16), pages, pages,
        sds((slots, pp), jnp.int32), sds((t,), jnp.int32),
        sds((t,), jnp.int32)).compile().as_text()
    assert "paged_ragged_v2" in text and "tpu_custom_call" in text


@pytest.mark.parametrize("block", [None, 256, 1280])
def test_serving_scan_kernel_compiles_in_place_for_a_v5e(topo, as_tpu,
                                                         block):
    """The state-space layers' scan (kernels/ssm_scan.py, PR 33) at
    Phi-4-mini-flash's served shape — 576 lanes, 65 slot rows of
    16 x 5120 f32, nine layers in one donated slab — at the block the
    kernel chooses and at the sweep's ends: one Mosaic call, and the
    program copies neither the slab nor a layer's row of it."""
    import re

    from flexflow_tpu.kernels import ssm_scan as ks
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    t, rows, n, d, layers = 576, 65, 16, 5120, 9
    assert ks.supported(t, n, d)

    def call(a_log, d_skip, u, dt, b, c, slab, slots, pos, starts, wslots,
             live):
        return ks.ssm_scan({"A_log": a_log, "D": d_skip}, u, dt, b, c, slab,
                           4, slots, pos, starts, wslots, live, block=block)

    lane = sds((t,), jnp.int32)
    compiled = jax.jit(call, donate_argnums=(6,)).lower(
        sds((n, d)), sds((d,)), sds((t, d)), sds((t, d)), sds((t, n)),
        sds((t, n)), sds((layers, rows, n, d)), lane, lane,
        sds((t,), jnp.bool_), lane, sds((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "ssm_scan" in text and text.count("tpu_custom_call") == 1
    copies = [line for line in text.splitlines()
              if re.search(r"\bcopy(-start)?\(", line)
              and re.search(r"f32\[(9,)?65,16,\d+\]", line)]
    assert not copies, copies
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 4 * layers * rows * n * d
    assert m.temp_size_in_bytes < 2**20


def test_delta_lane_kernel_compiles_in_place_for_a_v5e(topo, as_tpu):
    """The delta rule's lanes (kernels/gated_delta_scan.py, PR 51) at
    Qwen3-Next's served shape — 576 lanes, 65 slot rows of 4096 x 128
    f32 (32 value heads of 128 x 128), six layers in one donated slab,
    the plan made from the lane arrays: Mosaic takes the kernel (the
    gates as f32 scalars in SMEM, a 128 x 128 transpose, the states'
    copies in and out by hand), it is called on either side of the
    chunk-form blocks' loop, and the program copies neither the slab
    nor a layer's row of it."""
    import re

    from flexflow_tpu.kernels import gated_delta_scan as kd
    from flexflow_tpu.ops import gated_delta as gd
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    t, rows, h, dk, dv, layers = 576, 65, 32, 128, 128, 6
    assert kd.supported(t, h, dk, dv)

    def call(q, k, v, g, beta, slab, slots, pos, live, starts, n):
        plan = gd.lane_plan(slots, pos, live, starts, n)
        return kd.gated_delta_scan(q, k, v, g, beta, slab, 4, slots, pos,
                                   plan)

    lane, flag = sds((t,), jnp.int32), sds((t,), jnp.bool_)
    compiled = jax.jit(call, donate_argnums=(5,)).lower(
        sds((t, h, dk)), sds((t, h, dk)), sds((t, h, dv)), sds((t, h)),
        sds((t, h)), sds((layers, rows, h * dk, dv)), lane, lane, flag,
        flag, sds((), jnp.int32)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 2 and all("gated_delta_scan" in c for c in calls)
    assert len(re.findall(r"\bwhile\(", text)) == 1
    copies = [line for line in text.splitlines()
              if re.search(r"\bcopy(-start)?\(", line)
              and re.search(r"f32\[(6,)?(1,)?65,", line)]
    assert not copies, copies
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 4 * layers * rows * h * dk * dv
    # q, k, v of one block of the chunk form and `o`: no state slab
    assert m.temp_size_in_bytes < 2**26, m.temp_size_in_bytes


def _sds(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


@pytest.mark.parametrize("row_tile,f_tile,s,d,e,f", [
    (None, None, 4608, 2048, 64, 1024), (32, 256, 4608, 2048, 64, 1024),
    (None, None, 4352, 4096, 16, 4096)])
def test_fused_expert_kernel_compiles_at_the_served_shape(
        topo, as_tpu, row_tile, f_tile, s, d, e, f):
    """The gated expert (kernels/grouped_ffn.py, PR 37) at OLMoE's
    served shape — 4608 expert-sorted rows of 2048, 64 experts of 1024,
    bf16 — at the tiles the kernel chooses (a whole expert a grid step:
    24 MiB of double-buffered weights, inside the VMEM it asks for) and
    at the sweep's other end; and at Command A+'s share (PR 43): 4352
    rows of 4096, the 16 held experts of 4096 in four tiles of F (48
    MiB double-buffered): one Mosaic call, no grouped matmul."""
    from flexflow_tpu.kernels import grouped_ffn as kg
    from flexflow_tpu.ops import moe
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    rows, wg, wd = sds((s, d)), sds((e, d, f)), sds((e, f, d))
    assert moe.expert_impl(rows, wg) == "pallas"
    text = jax.jit(lambda *a: kg.grouped_ffn(
        *a, "silu", row_tile=row_tile, f_tile=f_tile)).lower(
        rows, sds((e,), jnp.int32), wg, wg, wd).compile().as_text()
    assert "grouped_ffn" in text and text.count("tpu_custom_call") == 1
    assert "ragged-dot" not in text


def test_olmoe_mixed_step_holds_one_expert_kernel_a_layer(topo, as_tpu):
    """OLMoE's mixed step at its served widths (576 lanes of 2048, 16
    heads of 128, experts of 1024, 8 a token, bf16; 4 layers of 8
    experts and a small vocabulary, so the parameters are quick to
    make): every layer's `experts` scope holds one Mosaic call and no
    grouped matmul, and no (4608, 1024) product of one reaches HBM."""
    import re

    from flexflow_tpu import FFConfig
    from flexflow_tpu.config import CompMode
    from flexflow_tpu.models.olmoe import build_olmoe_lm
    from flexflow_tpu.serve import ServeEngine
    layers = 4
    cfg = FFConfig(batch_size=1, kv_page_size=16, kv_num_pages=129,
                   serve_max_seqs=64, serve_prefill_budget=512,
                   serve_spec_decode=False, compute_dtype="bfloat16",
                   param_dtype="bfloat16", kv_dtype="bfloat16")
    lm = build_olmoe_lm(cfg, vocab_size=1024, max_seq_len=256, hidden=2048,
                        num_heads=16, num_layers=layers, num_experts=8,
                        experts_per_token=8, expert_dim=1024)
    lm.compile(comp_mode=CompMode.INFERENCE)
    engine = ServeEngine(lm)
    assert engine.expert_impl == engine.attn_impl == "pallas"
    assert (engine.mixed_width, engine.head_rows) == (576, 64)
    one = SingleDeviceSharding(topo.devices[0])
    c = engine.cache_cfg
    lane = jax.ShapeDtypeStruct((576,), jnp.int32, sharding=one)
    rows = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one)
    tables = jax.ShapeDtypeStruct((c.max_seqs, c.pages_per_seq), jnp.int32,
                                  sharding=one)
    text = jax.jit(engine._mixed_impl).lower(
        _sds(engine._step_params, one), _sds(engine._device_pool(), one),
        lane, lane, lane, lane, tables, lane, lane, rows, lane, rows
    ).compile().as_text()
    engine.close()
    calls = [line for line in text.splitlines()
             if "tpu_custom_call" in line and "grouped_ffn" in line]
    assert len(calls) == layers, len(calls)
    for i in range(layers):
        assert sum(f"serve_step/layer{i}/experts/" in line
                   for line in calls) == 1
    assert "ragged-dot" not in text
    assert not re.search(r"(bf16|f32)\[4608,1024\]", text)
    # the head runs over the emitting lanes' 64 rows: no array of all
    # the lanes by the vocabulary
    assert not re.search(r"(bf16|f32)\[576,1024\]", text)


def test_minicpm_sala_mixed_step_compiles_within_its_memory_plan(topo,
                                                                 as_tpu):
    """MiniCPM-SALA's mixed step at its served widths (544 lanes of
    4096; a sparse layer at 32 query / 2 key-value heads of 128 beside a
    lightning layer of 32 heads; 65,536 positions, 32,769 pages, 32
    slots; ONE sparse and ONE lightning layer and a small vocabulary,
    so the parameters are quick to make; PR 45): it compiles for a v5e
    with a paged call a key/value head for the lanes under dense_len
    and, under `sparse_attn`, a MASKED paged call a key/value head and
    stretch of lanes on a list made of the selection (PR 57: no gather
    of pool rows and no conditional there, each list inside the
    kernel's SMEM budget) and no other Mosaic call (the scores, the
    top-k and the matrix states are XLA's), the pool is updated in
    place, no copy of a pool leaf is laid out anew, and the step's
    temporaries stay under 1 GiB — the 16-layer configuration holds
    12.3 GiB of weights and cache."""
    from flexflow_tpu import FFConfig
    from flexflow_tpu.config import CompMode
    from flexflow_tpu.models.minicpm_sala import (LIGHTNING, MINICPM4,
                                                  build_minicpm_sala_lm)
    from flexflow_tpu.serve import ServeEngine
    from flexflow_tpu.serve.kv_cache import HybridPool
    from flexflow_tpu.kernels.paged_ragged_v2 import SMEM_LIST_WORDS
    from flexflow_tpu.serve.sparse_paged import STRAY_TILE
    cfg = FFConfig(batch_size=1, kv_page_size=16, kv_num_pages=32769,
                   serve_max_seqs=32, serve_prefill_budget=512,
                   serve_spec_decode=False, serve_prefix_cache=False,
                   compute_dtype="bfloat16", param_dtype="bfloat16",
                   kv_dtype="bfloat16")
    lm = build_minicpm_sala_lm(
        cfg, vocab_size=2048, max_seq_len=65536,
        mixer_types=(MINICPM4, LIGHTNING, LIGHTNING, LIGHTNING),
        layers_kept=[0, 1])
    lm.compile(comp_mode=CompMode.INFERENCE)
    engine = ServeEngine(lm)
    assert (engine.attn_impl, engine.scan_impl) == ("pallas", "jnp")
    assert (engine.mixed_width, engine.head_rows) == (544, 32)
    # the paged calls' grid is bounded by dense_len, not by the positions
    assert engine.dense_pages == 512 and engine.attn_max_items == 1568
    one = SingleDeviceSharding(topo.devices[0])
    c = engine.cache_cfg
    assert c.pages_per_seq == 4096
    pool = jax.eval_shape(lambda: HybridPool.alloc(c))   # 0.7 GiB unmade
    lane = jax.ShapeDtypeStruct((544,), jnp.int32, sharding=one)
    rows = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one)
    tables = jax.ShapeDtypeStruct((c.max_seqs, c.pages_per_seq), jnp.int32,
                                  sharding=one)
    lowered = jax.jit(engine._mixed_impl, donate_argnums=(1,)).lower(
        _sds(engine._step_params, one), _sds(pool, one), lane, lane, lane,
        lane, tables, lane, lane, rows, lane, rows)
    compiled = lowered.compile()
    engine.close()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert all("paged_ragged_v2" in c for c in calls)
    # the selected blocks go through the paged kernel from a list made
    # of the selection: a masked call a key/value head and stretch of
    # `select_call_lanes` lanes (the whole step's list with its mask
    # words passes the kernel's SMEM budget, so the lanes are cut
    # statically), every one under `sparse_attn`, on the pool's leaves
    g = engine.geometry
    stretches = -(-544 // g.select_call_lanes)
    masked = [c for c in calls if "paged_ragged_v2_select" in c]
    assert len(calls) - len(masked) == 2            # the dense lanes'
    assert len(masked) == 2 * stretches
    assert all("/sparse_attn/" in c for c in masked)
    words = 3 + g.select_block_pages + g.select_block_pages // 4
    assert (g.select_max_items + 1) * words <= SMEM_LIST_WORDS
    assert all(
        f"s32[{(g.select_max_items + 1) * g.select_block_pages}]" in c
        for c in masked)
    under = [line for line in text.splitlines() if "/sparse_attn/" in line]
    # no lane gathers a copy of its blocks: the gathers under the scope
    # are the lists' (rows of int32 words), none makes bf16 rows of the
    # pool, and nothing in the step is conditional
    gathers = [line for line in under if "gather" in line.split("(")[0]
               or re.search(r"fusion\(.*kind=kCustom.*gather", line)]
    assert gathers and all(re.search(r"= s32\[", line) for line in gathers)
    assert " conditional(" not in text
    assert "ragged-dot" not in text
    m = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pool))
    assert m.alias_size_in_bytes >= pool_bytes
    assert m.temp_size_in_bytes < 2**30, m.temp_size_in_bytes
    # no leaf of the pool is copied into another layout
    assert not re.search(r"= bf16\[2,32769,16,128\]\S* copy\(", text)
    # the scores' two passes (PR 55): the keys the lanes of a stretch
    # share are fetched in ONE gather of a (4096, 128) copy a stretch
    # and head (17 x 4096 rows) and meet the stretch's 32 x 16 rows in
    # one product a head; the stray lanes gather a copy a lane
    # (STRAY_TILE x 4096 rows) once in the program and once in the one
    # loop under `sparse_score`, whose trips are the further stretches
    # of strays; no conditional (its branch ran the same gather 3.6
    # times slower, and the step's logits left the reference's)
    score = [line for line in text.splitlines() if "/sparse_score/" in line]
    assert not any(" conditional(" in line for line in score)
    assert sum(bool(re.search(r"\bwhile\(", line)) for line in score) == 1
    shared = [line for line in score
              if "nd,jd->nj" in line and " convolution(" in line]
    assert len(shared) == 17 * 2
    assert all("= f32[32,16,4096]" in line for line in shared)
    fetch = {rows: [line for line in score if re.search(
        rf"= bf16\[{rows},128\]\S* fusion\(.*gather", line)]
        for rows in (17 * 4096, STRAY_TILE * 4096)}
    assert len(fetch[17 * 4096]) == 2
    assert not any("/while/" in line for line in fetch[17 * 4096])
    assert len(fetch[STRAY_TILE * 4096]) == 2 * 2
    assert sum("/while/" in line for line in fetch[STRAY_TILE * 4096]) == 2
    # the lightning layer's slab is written in place: its readers of the
    # OLD state and the new state go through one optimization barrier
    # before the write (PR 57: at 16 layers the compiler rematerialized
    # a slice of the old state AFTER the write; the barrier stands
    # through its scheduler and its rematerialization and is expanded
    # after them, so the compiled text no longer shows it), and nothing
    # in the compiled step reads a buffer after an in-place update of it
    assert len(re.findall(r"optimization_barrier", lowered.as_text())) == 1
    assert not _reads_after_in_place_write(text)


def _reads_after_in_place_write(text):
    """A scheduled module's text -> [(reader, the in-place op, their
    buffer)]: instructions of the entry computation that read a buffer
    AFTER a dynamic-update-slice or scatter fusion that updates it in
    place (its `aliasing_operands`: operand k with the output, whose
    index is the operands' count) has run."""
    import json
    roots, cur = {}, None
    for line in text.split("\n"):
        m = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\{\s*$", line)
        if m and not line.startswith(" "):
            cur = m.group(1)
        elif cur and line.lstrip().startswith("ROOT "):
            roots[cur] = line
    ops = []
    for line in text[text.index("\nENTRY "):].split("\n")[1:]:
        if line.startswith("}"):
            break
        m = re.match(r"\s*(?:ROOT )?(%[\w.\-]+) = (.*)", line)
        if m:
            body = re.split(r", (?:metadata|calls|backend_config)=",
                            m.group(2))[0]
            ops.append((m.group(1), m.group(2), re.findall(
                r"%[\w.\-]+", body.split("(", 1)[1]) if "(" in body else []))
    found = []
    for i, (name, rest, args) in enumerate(ops):
        at = rest.find('"aliasing_operands":')
        calls = re.search(r"calls=(%[\w.\-]+)", rest)
        root = roots.get(calls.group(1), "") if calls else ""
        if at < 0 or not (" dynamic-update-slice(" in root
                          or " scatter(" in root):
            continue
        lists = json.JSONDecoder().raw_decode(
            rest[at + len('"aliasing_operands":'):])[0]["lists"]
        for indices in ([int(k) for k in one["indices"]] for one in lists):
            if len(args) in indices:
                found += [(later, name, args[k]) for k in indices
                          if k < len(args)
                          for later, _, reads in ops[i + 1:]
                          if args[k] in reads]
    return found


_IN_PLACE = """HloModule step, is_scheduled=true

%fused_write (p0: f32[12,8], p1: f32[1,8]) -> f32[12,8] {
  %p0 = f32[12,8]{1,0} parameter(0)
  %p1 = f32[1,8]{1,0} parameter(1)
  %at = s32[] constant(11)
  %zero = s32[] constant(0)
  ROOT %dus = f32[12,8]{1,0} dynamic-update-slice(%p0, %p1, %at, %zero)
}

ENTRY %main (slab: f32[12,8], q: f32[1,8]) -> (f32[12,8], f32[1,8]) {
  %slab = f32[12,8]{1,0} parameter(0)
  %q = f32[1,8]{1,0} parameter(1)
  %old = f32[1,8]{1,0} slice(%slab), slice={[11:12], [0:8]}
  %new = f32[1,8]{1,0} add(%old, %q)
WRITE_AND_READ
  ROOT %out = (f32[12,8]{1,0}, f32[1,8]{1,0}) tuple(%written, %o)
}
"""
_WRITE = ('  %written = f32[12,8]{1,0} fusion(%slab, %new), kind=kLoop, '
          'calls=%fused_write, backend_config={"flag_configs":[],'
          '"aliasing_operands":{"lists":[{"indices":["0","2"]}]}}')
_READ = ['  %old.remat = f32[1,8]{1,0} slice(%slab), slice={[11:12], [0:8]}',
         '  %o = f32[1,8]{1,0} multiply(%old.remat, %q)']


@pytest.mark.parametrize("order,found", [
    # the read of the old slice rematerialized AFTER the in-place write:
    # what the 16-layer MiniCPM-SALA step's last lightning layer held
    ([_WRITE] + _READ, [("%old.remat", "%written", "%slab")]),
    # every reader of the old state before the write
    (_READ + [_WRITE], [])])
def test_a_read_after_an_in_place_write_is_found(order, found):
    text = _IN_PLACE.replace("WRITE_AND_READ", "\n".join(order))
    assert _reads_after_in_place_write(text) == found


def _loops(text):
    """The compiled step's `while(` lines but the binary searches'
    (`jnp.searchsorted` lowers to a loop of log2 trips: the work list's
    and the expert dispatch's, a few microseconds each)."""
    return [line for line in text.splitlines()
            if re.search(r"\bwhile\(", line) and "searchsorted" not in line]


def _scope_of(line):
    """The named scope a compiled instruction stands under: the last
    but one part of its `op_name`."""
    return re.search(r'op_name="([^"]*)"', line).group(1).split("/")[-2]


def _loop_bodies(text):
    """The text of every computation a `while(` names as its body or
    condition."""
    names = re.findall(r"(?:body|condition)=%([\w.\-]+)", text)
    return [text[text.index(f"\n%{n} ("):].split("\n}\n")[0] for n in names]


def test_qwen3_next_mixed_step_compiles_within_its_memory_plan(topo, as_tpu):
    """Qwen3-Next's mixed step at its served widths (576 lanes of 2048;
    a delta layer of 16 key / 32 value heads of 128 beside a gated
    attention layer at 16 query / 2 key-value heads of 256; 128 of 512
    top-10 experts of width 512 held; 32,768 positions, 49,153 pages,
    64 slots; ONE delta and ONE full layer and a small vocabulary, so
    the parameters are quick to make; PR 49): it compiles for a v5e with
    the paged kernel at a slab of 256 lanes (never compiled before this
    model) and the fused expert kernel at one tile of F, the delta
    rule's lanes in their own kernel (kernels/gated_delta_scan.py, PR
    51: the one kernel called on either side of the chunk-form blocks,
    whose loop is the only one under `delta_scan`), the pool — pages, states, tails
    — is updated in place with no copy of a state slab, and the step's
    temporaries stay under 0.5 GiB: the 8-layer configuration holds
    10.6 GiB of weights and cache."""
    import re
    from flexflow_tpu import FFConfig
    from flexflow_tpu.config import CompMode
    from flexflow_tpu.models.qwen3_next import build_qwen3_next_lm
    from flexflow_tpu.serve import ServeEngine
    from flexflow_tpu.serve.kv_cache import HybridPool
    cfg = FFConfig(batch_size=1, kv_page_size=16, kv_num_pages=49153,
                   serve_max_seqs=64, serve_prefill_budget=512,
                   serve_spec_decode=False, serve_prefix_cache=False,
                   compute_dtype="bfloat16", param_dtype="bfloat16",
                   kv_dtype="bfloat16")
    lm = build_qwen3_next_lm(
        cfg, vocab_size=2048, max_seq_len=32768, num_layers=2,
        full_attention_interval=2, experts_held=(0, 128))
    lm.compile(comp_mode=CompMode.INFERENCE)
    engine = ServeEngine(lm)
    assert (engine.attn_impl, engine.geometry.delta_impl,
            engine.expert_impl) == ("pallas", "pallas", "pallas")
    assert (engine.mixed_width, engine.head_rows) == (576, 64)
    one = SingleDeviceSharding(topo.devices[0])
    c = engine.cache_cfg
    assert (c.pages_per_seq, c.cache_bytes_per_token) == (2048, 2048)
    pool = jax.eval_shape(lambda: HybridPool.alloc(c))
    lane = jax.ShapeDtypeStruct((576,), jnp.int32, sharding=one)
    rows = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one)
    tables = jax.ShapeDtypeStruct((c.max_seqs, c.pages_per_seq), jnp.int32,
                                  sharding=one)
    compiled = jax.jit(engine._mixed_impl, donate_argnums=(1,)).lower(
        _sds(engine._step_params, one), _sds(pool, one), lane, lane, lane,
        lane, tables, lane, lane, rows, lane, rows).compile()
    engine.close()
    text = compiled.as_text()
    # no state slab is read after its in-place write (PR 57)
    assert not _reads_after_in_place_write(text)
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 5
    assert sum("paged_ragged_v2" in c for c in calls) == 1
    assert sum("grouped_ffn" in c for c in calls) == 2
    assert sum("gated_delta_scan" in c for c in calls) == 2
    # the chunk-form blocks' loop, of no trip where a step has none
    assert [_scope_of(line) for line in _loops(text)] == ["delta_scan"]
    assert "conditional(" not in text
    # the convolution's tail is written back with no scatter (PR 53)
    assert not re.search(r"scatter\(\S*bf16\[(1,)?65,24576\]", text)
    assert "ragged-dot" not in text
    m = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pool))
    assert m.alias_size_in_bytes >= pool_bytes
    assert m.temp_size_in_bytes < 2**29, m.temp_size_in_bytes
    # the one delta layer's state slab is moved on where it lies
    assert not re.search(r"= f32\[1,65,\S* copy\(", text)


def _qwen3_next_two_full():
    from flexflow_tpu.models.qwen3_next import build_qwen3_next_lm
    return dict(kv_num_pages=49153, serve_max_seqs=64), lambda cfg: \
        build_qwen3_next_lm(cfg, vocab_size=2048, max_seq_len=32768,
                            num_layers=4, full_attention_interval=2,
                            experts_held=(0, 128))


def _command_a_plus_rings():
    from flexflow_tpu.models.cmdaplus import build_cmdaplus_lm
    return dict(kv_num_pages=16385, serve_max_seqs=32), lambda cfg: \
        build_cmdaplus_lm(cfg, vocab_size=2048, max_seq_len=20480,
                          num_experts=8, expert_dim=1024, shared_experts=1,
                          experts_held=(0, 2))


def _phi4flash_rings():
    from flexflow_tpu.models.phi4flash import build_phi4flash_lm
    return dict(kv_num_pages=16385, serve_max_seqs=64), lambda cfg: \
        build_phi4flash_lm(cfg, vocab_size=2048, max_seq_len=8192,
                           num_layers=8, ff_dim=2560)


@pytest.mark.parametrize("build,calls,leaves", [
    (_qwen3_next_two_full, 2, [(2, 49153, 16, 512)]),
    (_command_a_plus_rings, 4, [(1, 16385, 16, 1024), (3, 9249, 16, 1024)]),
    (_phi4flash_rings, 4, [(1, 16385, 16, 1280), (2, 4161, 16, 1280)])],
    ids=["qwen3_next", "command_a_plus", "phi4flash"])
def test_a_packed_pool_s_paged_calls_read_their_leaf_where_it_lies(
        topo, as_tpu, build, calls, leaves):
    """The mixed step of the configurations whose pool leaves hold
    SEVERAL layers, at the served widths of the attention and of the
    pool (PR 50): Qwen3-Next with two full layers (2 x 49,153 pages of
    16 x 512), Command A+ with its three ring layers beside the full
    one (3 x 9,249 of 16 x 1024), Phi with two ring layers, the full
    layer and a cross layer (2 x 4,161 of 16 x 1280) — fewer layers, a
    small vocabulary and narrow experts / FFN, so the parameters are
    quick to make. Every paged call takes its leaf whole with the
    layer's first row as a scalar (KVPool.layer), ordered between the
    in-place scatters of `kv_write` by control dependences alone: the
    compiled text holds no copy, slice or fusion whose result is a
    layer's slab or a whole leaf (the scatters apart, which are in
    place: `alias_size_in_bytes` covers the pool), and the temporaries
    are smaller than ONE slab — at the parent they held the K and the V
    slab of a layer (Qwen3-Next: 1.70 GB, now 0.12)."""
    import re
    from flexflow_tpu import FFConfig
    from flexflow_tpu.config import CompMode
    from flexflow_tpu.serve import ServeEngine, mixers
    from flexflow_tpu.serve.kv_cache import HybridPool
    system, make = build()
    cfg = FFConfig(batch_size=1, kv_page_size=16, serve_prefill_budget=512,
                   serve_spec_decode=False, serve_prefix_cache=False,
                   compute_dtype="bfloat16", param_dtype="bfloat16",
                   kv_dtype="bfloat16", **system)
    lm = make(cfg)
    lm.compile(comp_mode=CompMode.INFERENCE)
    engine = ServeEngine(lm)
    assert engine.attn_impl == "pallas"
    assert mixers.paged_calls(engine.geometry) == {
        "paged_calls": calls, "paged_calls_in_place": calls}
    one = SingleDeviceSharding(topo.devices[0])
    c = engine.cache_cfg
    pool = jax.eval_shape(lambda: HybridPool.alloc(c))
    kv = [p for p in (pool.full, pool.window) if p is not None]
    assert [p.k.shape for p in kv] == leaves and all(p.heads for p in kv)
    lane = jax.ShapeDtypeStruct((engine.mixed_width,), jnp.int32,
                                sharding=one)
    rows = jax.ShapeDtypeStruct((engine.head_rows,), jnp.int32,
                                sharding=one)
    tables = jax.ShapeDtypeStruct((c.max_seqs, c.pages_per_seq), jnp.int32,
                                  sharding=one)
    compiled = jax.jit(engine._mixed_impl, donate_argnums=(1,)).lower(
        _sds(engine._step_params, one), _sds(pool, one), lane, lane, lane,
        lane, tables, lane, lane, rows, lane, rows).compile()
    engine.close()
    text = compiled.as_text()
    assert sum("tpu_custom_call" in line and "paged_ragged_v2" in line
               for line in text.splitlines()) == calls
    # a layer's slab, the leaf, and the leaf as rows of all its layers
    shapes = {s for n, p, slot, hd in leaves for s in (
        f"{p},{slot},{hd}", f"1,{p},{slot},{hd}", f"{n},{p},{slot},{hd}",
        f"{n * p},{slot},{hd}")}
    made = re.compile(r"= bf16\[(?:%s)\]\S* ([\w-]+)\(" % "|".join(shapes))
    moved = [line.strip()[:200] for line in text.splitlines()
             for op in made.findall(line)
             if op in ("copy", "copy-start", "slice", "dynamic-slice")
             or op == "fusion" and "kv_write" not in line]
    assert not moved, moved
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(pool))
    slab = min(2 * p * slot * hd for n, p, slot, hd in leaves if n > 1)
    assert m.temp_size_in_bytes < slab, (m.temp_size_in_bytes, slab)


def test_delta_lane_kernel_compiles_at_olmo_hybrid_s_heads(topo, as_tpu):
    """The delta rule's lanes at Olmo-Hybrid's served shape (PR 52) —
    544 lanes, 30 heads of 96 x 192, 33 slot rows, twelve layers in one
    donated slab laid out in head PAIRS, (1440, 384) a state: Mosaic
    takes the kernel with q's heads at sublane 32 of the shared tile,
    the transposed tile's first 96 rows and a pair's two heads selected
    by lane; it is called on either side of the chunk-form blocks' loop,
    the slab is moved on where it lies (no copy of it, of a layer's row
    or of a re-laid view of either: splitting a row's 384 lanes into 2
    x 192 would copy all of it), and the temporaries are the lanes' own
    rows."""
    import re

    from flexflow_tpu.kernels import gated_delta_scan as kd
    from flexflow_tpu.ops import gated_delta as gd
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    t, rows, h, dk, dv, layers = 544, 33, 30, 96, 192, 12
    assert kd.supported(t, h, dk, dv)
    state = gd.state_shape(h, dk, dv)
    assert state == (1440, 384)

    def call(q, k, v, g, beta, slab, slots, pos, live, starts, n):
        plan = gd.lane_plan(slots, pos, live, starts, n)
        return kd.gated_delta_scan(q, k, v, g, beta, slab, 4, slots, pos,
                                   plan)

    lane, flag = sds((t,), jnp.int32), sds((t,), jnp.bool_)
    compiled = jax.jit(call, donate_argnums=(5,)).lower(
        sds((t, h, dk)), sds((t, h, dk)), sds((t, h, dv)), sds((t, h)),
        sds((t, h)), sds((layers, rows) + state), lane, lane, flag, flag,
        sds((), jnp.int32)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 2 and all("gated_delta_scan" in c for c in calls)
    assert len(re.findall(r"\bwhile\(", text)) == 1
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(r"\b(copy|copy-start|reshape)\(", line)
             and re.search(r"= f32\[(12,)?(1,)?33,", line)]
    assert not moved, moved
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 4 * layers * rows * h * dk * dv
    assert m.temp_size_in_bytes < 2**26, m.temp_size_in_bytes


def test_olmo_hybrid_mixed_step_compiles_within_its_memory_plan(topo,
                                                                as_tpu):
    """Olmo-Hybrid's mixed step at its served widths (544 lanes of 3840;
    a delta layer of 30 heads of 96 x 192 beside a full layer of 30 / 30
    heads of 128 on a head-packed pool of 4,916 pages, 15,360 B a token
    a layer; the dense feed-forward 11,008 wide; 32,768 positions, 32
    slots; ONE delta and ONE full layer and a small vocabulary, so the
    parameters are quick to make; PR 52): it compiles for a v5e with
    the paged kernel at a head count that is no power of two, reading
    its leaf where it lies, and the delta rule's lanes in their kernel;
    the pool — pages, states, tails — is updated in place with no copy
    of the state slab, and the step's temporaries stay under ONE state
    slab of the twelve-layer configuration's (876 MB), which holds 13
    GiB of weights and cache."""
    import re
    from flexflow_tpu import FFConfig
    from flexflow_tpu.config import CompMode
    from flexflow_tpu.models.olmo_hybrid import build_olmo_hybrid_lm
    from flexflow_tpu.serve import ServeEngine, mixers
    from flexflow_tpu.serve.kv_cache import HybridPool
    cfg = FFConfig(batch_size=1, kv_page_size=16, kv_num_pages=4916,
                   serve_max_seqs=32, serve_prefill_budget=512,
                   serve_spec_decode=False, serve_prefix_cache=False,
                   compute_dtype="bfloat16", param_dtype="bfloat16",
                   kv_dtype="bfloat16")
    lm = build_olmo_hybrid_lm(
        cfg, vocab_size=2048, max_seq_len=32768, num_layers=2,
        types=["linear_attention", "full_attention"])
    lm.compile(comp_mode=CompMode.INFERENCE)
    engine = ServeEngine(lm)
    assert (engine.attn_impl, engine.geometry.delta_impl) == ("pallas",
                                                              "pallas")
    assert (engine.mixed_width, engine.head_rows) == (544, 32)
    assert engine.geometry.delta_state["delta_state_slot_bytes"] == 2211840
    assert mixers.paged_calls(engine.geometry) == {
        "paged_calls": 1, "paged_calls_in_place": 1}
    one = SingleDeviceSharding(topo.devices[0])
    c = engine.cache_cfg
    assert (c.pages_per_seq, c.cache_bytes_per_token, c.packed_heads) == (
        2048, 15360, True)
    pool = jax.eval_shape(lambda: HybridPool.alloc(c))
    assert pool.state.shape == (1, 33, 1440, 384)
    assert pool.full.k.shape == (1, 4916, 16, 3840)
    lane = jax.ShapeDtypeStruct((544,), jnp.int32, sharding=one)
    rows = jax.ShapeDtypeStruct((32,), jnp.int32, sharding=one)
    tables = jax.ShapeDtypeStruct((c.max_seqs, c.pages_per_seq), jnp.int32,
                                  sharding=one)
    compiled = jax.jit(engine._mixed_impl, donate_argnums=(1,)).lower(
        _sds(engine._step_params, one), _sds(pool, one), lane, lane, lane,
        lane, tables, lane, lane, rows, lane, rows).compile()
    engine.close()
    text = compiled.as_text()
    # no state slab is read after its in-place write (PR 57)
    assert not _reads_after_in_place_write(text)
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 3
    assert sum("paged_ragged_v2" in c for c in calls) == 1
    assert sum("gated_delta_scan" in c for c in calls) == 2
    # the step's ONLY loop is the chunk-form blocks' (the convolution's
    # tail write-back was one of 544 one-row updates until PR 53), and
    # nothing scatters into the tail or updates it inside a loop
    assert [_scope_of(line) for line in _loops(text)] == ["delta_scan"]
    assert "conditional(" not in text
    assert not re.search(r"scatter\(\S*bf16\[(1,)?33,34560\]", text)
    for body in _loop_bodies(text):
        assert "bf16[33,34560]" not in body and "bf16[1,33,34560]" not in body
    m = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pool))
    assert m.alias_size_in_bytes >= pool_bytes
    assert m.temp_size_in_bytes < 12 * 33 * 2211840, m.temp_size_in_bytes
    # neither the state slab nor the pages' leaf is copied or re-laid
    assert not re.search(r"= f32\[1,33,\S* (copy|reshape)\(", text)
    assert not re.search(r"= bf16\[(1,)?4916,16,3840\]\S* copy\(", text)


@pytest.mark.parametrize("lanes,channels,slots", [
    (544, 11520, 32),      # Olmo-Hybrid: XLA had EXPANDED the scatter here
    (576, 8192, 64),       # Qwen3-Next: a native scatter( until PR 53
    (576, 5120, 64),       # Phi-4-mini-flash: likewise
])
def test_the_convolution_s_tail_write_back_is_no_loop_and_no_scatter(
        topo, lanes, channels, slots):
    """`ops/ssm.py::segmented_conv` alone at the three served shapes,
    its tail donated: the write-back follows the runs — a gather of at
    most `slots` rows and a select, one static update of the tail in
    place — so the compiled text holds neither a `while(` (the 544
    one-row `dynamic-update-slice`s a layer of Olmo-Hybrid's step, 43 ms
    of 123.5; PR 53) nor a `scatter(`."""
    from flexflow_tpu.ops import ssm
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=one)
    lane = sds((lanes,), jnp.int32)
    compiled = jax.jit(ssm.segmented_conv, donate_argnums=(2,)).lower(
        {"conv_w": sds((4, channels), jnp.bfloat16)},
        sds((lanes, channels), jnp.bfloat16),
        sds((slots + 1, 3 * channels), jnp.bfloat16), lane, lane, lane,
        sds((slots,), jnp.int32)).compile()
    text = compiled.as_text()
    assert not re.search(r"\bwhile\(", text)
    assert not re.search(r"\bscatter\(", text)
    assert len(re.findall(r"dynamic-update-slice\(", text)) == 1
    # the tail is updated where it lies
    assert compiled.memory_analysis().alias_size_in_bytes > 0


def test_ssd_lane_kernel_compiles_in_place_for_a_v5e(topo, as_tpu):
    """Mamba-2's lanes at Falcon-H1's served shape (PR 56) — 608 lanes,
    32 heads of 128 x 256 in 2 groups, 97 slot rows, six layers in one
    donated slab of (256, 4096) states, 4 MiB each: Mosaic takes the
    kernel with three states in VMEM and B's and C's eight rows of one
    transposed tile; it is called on either side of the chunk-form
    blocks' loop, the slab is moved on where it lies (no copy of it or
    of a layer's row), and the temporaries are the lanes' own rows."""
    import functools
    import re

    from flexflow_tpu.kernels import ssd_scan as ks
    from flexflow_tpu.ops import gated_delta as gd
    from flexflow_tpu.ops import ssd
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    t, rows, h, p, g, n, layers = 608, 97, 32, 128, 2, 256, 6
    assert ks.supported(t, h, p, g, n)
    assert ssd.Dims(h, p, g, n).state_shape == (256, 4096)

    def call(v, b, c, la, slab, slots, pos, live, starts, count):
        plan = gd.lane_plan(slots, pos, live, starts, count)
        return ssd.segmented(v, b, c, la, slab, 4, slots, pos, plan,
                             lane_pass=functools.partial(ks.lane_pass))

    lane, flag = sds((t,), jnp.int32), sds((t,), jnp.bool_)
    compiled = jax.jit(call, donate_argnums=(4,)).lower(
        sds((t, h, p)), sds((t, g, n)), sds((t, g, n)), sds((t, h)),
        sds((layers, rows, n, h * p)), lane, lane, flag, flag,
        sds((), jnp.int32)).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 2 and all("ssd_scan" in c for c in calls)
    assert len(re.findall(r"\bwhile\(", text)) == 1
    moved = [line.strip()[:160] for line in text.splitlines()
             if re.search(r"\b(copy|copy-start|reshape)\(", line)
             and re.search(r"= f32\[(6,)?(1,)?97,", line)]
    assert not moved, moved
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 4 * layers * rows * n * h * p
    assert m.temp_size_in_bytes < 2**26, m.temp_size_in_bytes


def test_falcon_h1_mixed_step_compiles_within_its_memory_plan(topo, as_tpu):
    """Falcon-H1's mixed step at its served widths (608 lanes of 5120;
    in EVERY layer 32 Mamba-2 heads of 128 x 256 on a slab of 97 rows
    beside 20 / 4 attention heads of 128 on a head-packed pool of 8,193
    pages, 2,048 B a token a layer; the dense feed-forward 21,504 wide;
    8,192 positions, 96 slots; TWO layers and a small vocabulary, so the
    parameters are quick to make; PR 56): it compiles for a v5e with the
    paged kernel at 20 / 4 heads reading its leaf where it lies and
    Mamba-2's lanes in their kernel, twice a layer; the pool — pages,
    states, tails — is updated in place with no copy of the state slab
    or of a pages' leaf, a layer's only loop is the chunk-form blocks'
    (no scatter expanded to a loop of one-row updates), and the step's
    temporaries stay under a quarter of ONE layer's slab of states."""
    import re
    from flexflow_tpu import FFConfig
    from flexflow_tpu.config import CompMode
    from flexflow_tpu.models.falcon_h1 import build_falcon_h1_lm
    from flexflow_tpu.serve import ServeEngine, mixers
    from flexflow_tpu.serve.kv_cache import HybridPool
    cfg = FFConfig(batch_size=1, kv_page_size=16, kv_num_pages=8193,
                   serve_max_seqs=96, serve_prefill_budget=512,
                   serve_spec_decode=False, serve_prefix_cache=False,
                   compute_dtype="bfloat16", param_dtype="bfloat16",
                   kv_dtype="bfloat16")
    lm = build_falcon_h1_lm(
        cfg, vocab_size=2048, max_seq_len=8192, num_layers=2,
        embedding_multiplier=5.657, lm_head_multiplier=0.0078125,
        ssm_in_multiplier=0.25,
        ssm_multipliers=(0.354, 0.25, 0.177, 0.5, 0.354),
        ssm_out_multiplier=0.0884, attention_out_multiplier=0.0375,
        key_multiplier=0.011, mlp_multipliers=(0.177, 0.0112))
    lm.compile(comp_mode=CompMode.INFERENCE)
    engine = ServeEngine(lm)
    assert (engine.attn_impl, engine.scan_impl) == ("pallas", "pallas")
    assert (engine.mixed_width, engine.head_rows) == (608, 96)
    assert engine.geometry.delta_state["ssd_state_slot_bytes"] == 4194304
    assert mixers.paged_calls(engine.geometry) == {
        "paged_calls": 2, "paged_calls_in_place": 2}
    one = SingleDeviceSharding(topo.devices[0])
    c = engine.cache_cfg
    assert (c.pages_per_seq, c.cache_bytes_per_token, c.packed_heads) == (
        512, 4096, True)
    pool = jax.eval_shape(lambda: HybridPool.alloc(c))
    assert pool.state.shape == (2, 97, 256, 4096)
    assert pool.tail.shape == (2, 97, 15360)
    assert pool.full.k.shape == (2, 8193, 16, 512)
    lane = jax.ShapeDtypeStruct((608,), jnp.int32, sharding=one)
    rows = jax.ShapeDtypeStruct((96,), jnp.int32, sharding=one)
    tables = jax.ShapeDtypeStruct((c.max_seqs, c.pages_per_seq), jnp.int32,
                                  sharding=one)
    compiled = jax.jit(engine._mixed_impl, donate_argnums=(1,)).lower(
        _sds(engine._step_params, one), _sds(pool, one), lane, lane, lane,
        lane, tables, lane, lane, rows, lane, rows).compile()
    engine.close()
    text = compiled.as_text()
    # no state slab is read after its in-place write (PR 57)
    assert not _reads_after_in_place_write(text)
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 6
    assert sum("paged_ragged_v2" in c for c in calls) == 2
    assert sum("ssd_scan" in c for c in calls) == 4
    # a layer's ONLY loop is the chunk-form blocks'; nothing scatters
    # into the tail or updates it inside a loop
    assert [_scope_of(line) for line in _loops(text)] == ["ssm_scan"] * 2
    assert "conditional(" not in text
    assert not re.search(r"scatter\(\S*bf16\[(2,)?97,15360\]", text)
    for body in _loop_bodies(text):
        assert "bf16[97,15360]" not in body and "bf16[2,97,15360]" not in body
    m = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pool))
    assert m.alias_size_in_bytes >= pool_bytes
    assert m.temp_size_in_bytes < 97 * 4194304 // 4, m.temp_size_in_bytes
    # neither the state slab nor a pages' leaf is copied or re-laid
    assert not re.search(r"= f32\[2,97,\S* (copy|reshape)\(", text)
    assert not re.search(r"= f32\[(1,)?97,256,4096\]\S* copy\(", text)
    assert not re.search(r"= bf16\[(2,)?8193,16,512\]\S* copy\(", text)


def test_lfm2_moe_mixed_step_compiles_within_its_memory_plan(topo, as_tpu):
    """LFM2-MoE's mixed step at its served widths (768 lanes of 2048; a
    gated short convolution on a tail slab of 257 rows x 4,096 and NO
    state, 32 / 8 attention heads of 64 with per-head QK-norm on a
    head-packed pool of 32,769 pages, 2,048 B a token a layer, a dense
    feed-forward 11,776 wide, 64 experts of width 1,536 chosen top-4
    under a selection bias; 4,096 positions, 256 slots; THREE layers — a
    dense convolution layer, an attention and a convolution layer that
    route — and a small vocabulary, so the parameters are quick to make;
    PR 58): it compiles for a v5e with the paged kernel at 32 / 8 heads
    reading its leaf where it lies and the fused expert kernel once a
    routing layer; the pool — pages and tails — is updated in place with
    no copy of a leaf or of an expert stack, nothing loops but the
    binary searches (no scatter expanded to a loop of one-row updates in
    the tail's write-back), and the step's temporaries stay under 1
    GiB."""
    import re
    from flexflow_tpu import FFConfig
    from flexflow_tpu.config import CompMode
    from flexflow_tpu.models.lfm2_moe import build_lfm2_moe_lm
    from flexflow_tpu.serve import ServeEngine, mixers
    from flexflow_tpu.serve.kv_cache import HybridPool
    cfg = FFConfig(batch_size=1, kv_page_size=16, kv_num_pages=32769,
                   serve_max_seqs=256, serve_prefill_budget=512,
                   serve_spec_decode=False, serve_prefix_cache=False,
                   compute_dtype="bfloat16", param_dtype="bfloat16",
                   kv_dtype="bfloat16")
    lm = build_lfm2_moe_lm(
        cfg, vocab_size=2048, max_seq_len=4096,
        layer_types=["conv", "full_attention", "conv"], num_dense_layers=1,
        expert_bias_std=0.015)
    lm.compile(comp_mode=CompMode.INFERENCE)
    engine = ServeEngine(lm)
    assert engine.attn_impl == "pallas" and engine.scan_impl is None
    assert engine.arch.expert_impl(768) == "pallas"
    assert (engine.mixed_width, engine.head_rows) == (768, 256)
    assert engine.geometry.delta_state["conv_tail_slot_bytes"] == 8192
    assert mixers.paged_calls(engine.geometry) == {
        "paged_calls": 1, "paged_calls_in_place": 1}
    one = SingleDeviceSharding(topo.devices[0])
    c = engine.cache_cfg
    assert (c.pages_per_seq, c.cache_bytes_per_token, c.packed_heads) == (
        256, 2048, True)
    pool = jax.eval_shape(lambda: HybridPool.alloc(c))
    assert pool.state is None and pool.window is None
    assert pool.tail.shape == (2, 257, 4096)
    assert pool.full.k.shape == (1, 32769, 16, 512)
    lane = jax.ShapeDtypeStruct((768,), jnp.int32, sharding=one)
    rows = jax.ShapeDtypeStruct((256,), jnp.int32, sharding=one)
    tables = jax.ShapeDtypeStruct((c.max_seqs, c.pages_per_seq), jnp.int32,
                                  sharding=one)
    compiled = jax.jit(engine._mixed_impl, donate_argnums=(1,)).lower(
        _sds(engine._step_params, one), _sds(pool, one), lane, lane, lane,
        lane, tables, lane, lane, rows, lane, rows).compile()
    engine.close()
    text = compiled.as_text()
    assert not _reads_after_in_place_write(text)
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert sum("paged_ragged_v2" in c for c in calls) == 1
    assert sum("grouped_ffn" in c for c in calls) == 2
    assert len(calls) == 3
    # nothing loops; nothing scatters into the tail or updates it inside
    # a loop
    assert _loops(text) == []
    assert "conditional(" not in text
    assert not re.search(r"scatter\(\S*bf16\[(2,)?257,4096\]", text)
    for body in _loop_bodies(text):
        assert "bf16[257,4096]" not in body and "bf16[2,257,4096]" not in body
    m = compiled.memory_analysis()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pool))
    assert m.alias_size_in_bytes >= pool_bytes
    assert m.temp_size_in_bytes < 2**30, m.temp_size_in_bytes
    # neither a pages' leaf nor an expert stack is copied or re-laid.
    # The tail slab (16 MiB at the served 8 layers) is staged WHOLE in
    # the chip's fast memory for the step — one copy in (S(1)), every
    # layer's gather and update there, one copy out: two passes over it
    # a step, not two a layer
    assert len(re.findall(r"= bf16\[2,257,4096\]\S* copy\(", text)) <= 2
    assert not re.search(r"= bf16\[(1,)?257,4096\]\S* copy\(", text)
    assert not re.search(r"= bf16\[(1,)?32769,16,512\]\S* copy\(", text)
    assert not re.search(r"= bf16\[64,(2048,1536|1536,2048)\]\S* copy\(",
                         text)
