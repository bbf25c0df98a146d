"""The seam between the serve engine and the mixer kinds (ISSUE 48,
serve/mixers.py): every kind a description names has a body; the paged
calls a step makes are derived from the kinds; the engine names no
kind; the rules the device and the host both need are one function
over `xp`, and the host's count of each work list is the device list's
own length; the gated memory unit reads what the memory layer's body
returned; the host's counts on a fixed list of submissions are the
numbers the engine counted before the move.
"""

import ast
import os

import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.kernels.paged_ragged_v2 import PALLAS_INTERPRET
from flexflow_tpu.serve import ServeEngine
from flexflow_tpu.serve import engine as E
from flexflow_tpu.serve import mixers as M
from flexflow_tpu.serve.arch import (ATTN, CONV, CROSS, DELTA, FULL, GMU,
                                     LINEAR, SPARSE, SSD_ATTN, SSM, WINDOW)
from flexflow_tpu.serve.kv_cache import ring_tables

KINDS = (ATTN, WINDOW, FULL, CROSS, SSM, GMU, LINEAR, SPARSE, DELTA,
         SSD_ATTN, CONV)
# description -> (the test module whose `_lm` builds it, the paged calls
# a step of that build as the hand-written `attn_calls()` answered
# before ISSUE 48)
BUILDS = {"transformer_lm": ("test_paged_work_list", (3, 0)),
          "olmoe": ("test_olmoe", (2, 0)),
          "phi4flash": ("test_phi4flash", (2, 2)),
          "command_a_plus": ("test_cmdaplus", (1, 3)),
          "minicpm_sala": ("test_minicpm_sala", (4, 0)),
          # since PR 49: a description that never had such a method
          "qwen3_next": ("test_qwen3_next", (1, 0)),
          # since PR 58: one attention layer of six, the others tails
          "lfm2_moe": ("test_lfm2_moe", (1, 0))}
_lms, _engines = {}, {}


def _engine(kind, fresh=False):
    """A jnp engine of a description: one kept for the module, or a new
    one over the same model (its slots and pages as a boot leaves
    them)."""
    if kind not in _lms:
        mod = __import__(BUILDS[kind][0])
        _lms[kind] = mod._count_engine("opt").model \
            if kind == "transformer_lm" else mod._lm()
    if fresh:
        return ServeEngine(_lms[kind], use_pallas=False)
    if kind not in _engines:
        _engines[kind] = ServeEngine(_lms[kind], use_pallas=False)
    return _engines[kind]


# ------------------------------------------- (a) the table and the calls
@pytest.mark.parametrize("kind", list(BUILDS))
def test_every_kind_has_a_body_and_the_calls_are_derived(kind):
    eng = _engine(kind)
    arch = eng.arch
    assert arch.kind == kind
    kinds = [arch.mixer(i) for i in range(arch.num_layers)]
    assert set(kinds) <= set(M.BODIES)
    assert eng.geometry.attn_calls == M.attn_calls(arch) == BUILDS[kind][1]
    # the names other code reads are the geometry's, not a second rule
    g = eng.geometry
    assert (eng.scan_impl, eng.dense_pages, eng.attn_block_pages,
            eng.attn_max_items, eng.window_max_items) == (
        g.scan_impl, g.dense_pages, g.block_pages, g.attn_max_items,
        g.window_max_items)
    assert not hasattr(arch, "attn_calls")      # defined once


class _Kinds:
    def __init__(self, kinds, kv_heads=1):
        self.kinds, self.kv_heads = list(kinds), kv_heads
        self.num_layers = len(self.kinds)

    def mixer(self, i):
        return self.kinds[i]


@pytest.mark.parametrize("name,arch,calls", [
    ("OPT-1.3B", _Kinds([ATTN] * 24), (24, 0)),
    ("OLMoE, 16 layers", _Kinds([ATTN] * 16), (16, 0)),
    # the full layer and seven cross layers, eight window layers
    ("Phi-4-mini-flash", None, (8, 8)),
    ("Command A+, 4 layers", _Kinds([WINDOW] * 3 + [FULL], 8), (1, 3)),
    # four sparse layers of two key/value heads
    ("MiniCPM-SALA, 16 layers",
     _Kinds(([SPARSE] + [LINEAR] * 3) * 4, 2), (8, 0)),
    # two attention layers of ten: a convolution layer makes no call
    ("LFM2-24B-A2B, 10 layers",
     _Kinds([CONV] * 2 + ([FULL] + [CONV] * 3) * 2, 8), (2, 0)),
])
def test_the_calls_a_step_at_the_served_depths(name, arch, calls):
    if arch is None:
        from flexflow_tpu.models.phi4flash import mixer_kinds
        arch = _Kinds(mixer_kinds(32), 10)
    assert M.attn_calls(arch) == calls


def test_the_table_is_the_eleven_kinds():
    assert set(M.BODIES) == set(KINDS) and len(set(KINDS)) == 11


# ------------------------------------------------ (b) the arrows, one way
@pytest.mark.parametrize("module", ["mixers", "arch"])
def test_mixers_and_arch_import_neither_engine_nor_scheduler(module):
    path = os.path.join(os.path.dirname(M.__file__), module + ".py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add(node.module or "")
            names |= {a.name for a in node.names}
    leaves = {n.rsplit(".", 1)[-1] for n in names}
    assert not leaves & {"engine", "scheduler"}, leaves


def test_the_engine_names_no_mixer_kind():
    held = set(vars(E))
    assert not held & {"SSM", "GMU", "LINEAR", "SPARSE", "WINDOW", "FULL",
                       "CROSS", "DELTA"}
    # no import of a mixer's ops or kernels, no forwarding method
    assert not held & {"ssm", "ssm_scan", "linear_attention",
                       "gated_delta", "paged_sparse_attention",
                       "stride_keys"}
    for gone in ("_attn_layer", "_ssm_layer", "_linear_layer",
                 "_sparse_layer", "_hybrid_lanes", "_dense_lanes"):
        assert not hasattr(E.ServeEngine, gone), gone
    assert E.LIVE_COUNTS is M.LIVE_COUNTS
    assert E.SELECT_COUNTS is M.SELECT_COUNTS


# --------------- (c) one rule for the device and the host, and (e) counts
def _drive(eng, sizes, new, on_pack=None):
    """submit, then step() until nothing is left (no clock) -> every
    dispatched step's events, in order."""
    rng = np.random.RandomState(3)
    steps = []
    pack = E.ServeSession._pack

    def spy(self, plan):
        out = pack(self, plan)
        if on_pack is not None:
            on_pack(out)
        return out

    E.ServeSession._pack = spy
    try:
        with E.ServeSession(eng) as s:
            for n in sizes:
                s.submit(list(rng.randint(1, eng.vocab_size, size=n)), new)
            while s.has_work():
                ev = s.step()
                if ev is not None and ev.dispatched:
                    steps.append(ev)
    finally:
        E.ServeSession._pack = pack
    return steps


@pytest.mark.parametrize("kind", ["minicpm_sala", "phi4flash"])
def test_the_device_lists_are_the_lists_the_host_counted(kind):
    """`walked` and `ring_tables` give equal arrays under numpy and
    jax.numpy on every packed plan, and the grid steps the host counts
    are the device lists' own lengths over the calls that walk them:
    what would have caught a drift between `_pack` and the step."""
    eng = _engine(kind)
    g = eng.geometry
    # the same geometry with a paged kernel: its step builds the lists
    gk = M.geometry(eng.arch, eng.cache_cfg, width=eng.mixed_width,
                    attn_impl=PALLAS_INTERPRET, block_kv=eng.attn_block_kv)
    assert (gk.attn_max_items, gk.window_max_items, gk.attn_calls) == (
        g.attn_max_items, g.window_max_items, g.attn_calls)
    sides = set()

    def on_pack(out):
        arrays, work = out[0], out[-1]
        _, positions, write_pages, write_offs, tables, slots, lens = \
            arrays[:7]
        live = out[2]
        host = M.walked(g, tables, positions, lens, np)
        dev = M.walked(g, jnp.asarray(tables), jnp.asarray(positions),
                       jnp.asarray(lens), jnp)
        for a, b in zip(host, dev):
            np.testing.assert_array_equal(a, np.asarray(b))
        lanes = M.step_lanes(gk, *map(jnp.asarray, (
            positions, write_pages, write_offs, tables, slots, lens)))
        assert int(lanes.work.count) == work["total"]
        full_calls, window_calls = g.attn_calls
        grid = full_calls * int(lanes.work.count)
        if window_calls:
            grid += window_calls * int(lanes.window_work.count)
        assert work["grid_steps"] == grid
        edge = eng.arch.dense_len or eng.arch.window
        sides.update((positions[:live] >= edge).tolist())

    steps = _drive(eng, (30, 3, 100 if kind == "minicpm_sala" else 70), 4,
                   on_pack)
    assert len(steps) > 6 and sides == {True, False}
    if g.rings is not None:
        np.testing.assert_array_equal(
            g.rings, np.asarray(ring_tables(eng.cache_cfg, jnp)))
        assert any(ev.lanes_past_window for ev in steps)
    else:
        assert any(ev.sparse_lanes for ev in steps)


# every dispatched step's COUNTED for prompts of 30, 3 and 70 tokens (40
# where the model serves 64 positions) and 4 new tokens each, as the
# engine counted them before the counts moved (the parent of ISSUE 48,
# same builds)
COUNTED = ("grid_steps", "live_steps", "short_steps", "live_rows",
           "kv_bytes_read", "attn_items", "attn_rows", "state_bytes",
           "window_kv_bytes", "ssm_runs", "lanes_past_window",
           "sparse_lanes", "blocks_selected")
EXPECTED = {}
EXPECTED["transformer_lm"] = [
    (3, 3, 0, 36, 49152, 1, 12, 0, 0, 0, 0, 0, 0),
    (3, 3, 0, 36, 49152, 1, 12, 0, 0, 0, 0, 0, 0),
    (12, 9, 0, 36, 58368, 3, 12, 0, 0, 0, 0, 0, 0),
    (12, 9, 0, 42, 67584, 3, 14, 0, 0, 0, 0, 0, 0),
    (12, 9, 0, 42, 79872, 3, 14, 0, 0, 0, 0, 0, 0),
    (12, 9, 0, 42, 89088, 3, 14, 0, 0, 0, 0, 0, 0),
    (6, 3, 0, 3, 52224, 1, 1, 0, 0, 0, 0, 0, 0),
    (6, 3, 0, 3, 52224, 1, 1, 0, 0, 0, 0, 0, 0),
    (6, 3, 0, 3, 52224, 1, 1, 0, 0, 0, 0, 0, 0),
    (6, 3, 0, 3, 52224, 1, 1, 0, 0, 0, 0, 0, 0),
]
EXPECTED["olmoe"] = [
    (6, 4, 0, 64, 294912, 2, 32, 0, 0, 0, 0, 0, 0),
    (10, 8, 0, 66, 327680, 4, 33, 0, 0, 0, 0, 0, 0),
    (10, 8, 0, 68, 360448, 4, 34, 0, 0, 0, 0, 0, 0),
    (10, 6, 0, 18, 376832, 3, 9, 0, 0, 0, 0, 0, 0),
    (8, 4, 0, 4, 360448, 2, 2, 0, 0, 0, 0, 0, 0),
    (6, 2, 0, 2, 278528, 1, 1, 0, 0, 0, 0, 0, 0),
    (6, 2, 0, 2, 278528, 1, 1, 0, 0, 0, 0, 0, 0),
]
EXPECTED["phi4flash"] = [
    (4, 4, 0, 96, 131072, 1, 24, 58368, 65536, 1, 8, 0, 0),
    (16, 12, 0, 96, 163840, 3, 24, 175104, 81920, 3, 6, 0, 0),
    (16, 12, 8, 104, 188416, 3, 26, 175104, 94208, 3, 24, 0, 0),
    (16, 12, 8, 104, 200704, 3, 26, 175104, 94208, 3, 25, 0, 0),
    (16, 12, 8, 36, 196608, 3, 9, 175104, 86016, 3, 8, 0, 0),
    (8, 4, 4, 4, 139264, 1, 1, 58368, 69632, 1, 1, 0, 0),
    (8, 4, 4, 4, 139264, 1, 1, 58368, 69632, 1, 1, 0, 0),
    (8, 4, 4, 4, 139264, 1, 1, 58368, 69632, 1, 1, 0, 0),
]
EXPECTED["command_a_plus"] = [
    (4, 4, 0, 96, 131072, 1, 24, 0, 98304, 0, 8, 0, 0),
    (16, 12, 0, 96, 163840, 3, 24, 0, 122880, 0, 6, 0, 0),
    (16, 12, 8, 104, 188416, 3, 26, 0, 141312, 0, 24, 0, 0),
    (16, 12, 8, 104, 194560, 3, 26, 0, 141312, 0, 25, 0, 0),
    (16, 12, 8, 36, 184320, 3, 9, 0, 129024, 0, 8, 0, 0),
    (8, 4, 4, 4, 139264, 1, 1, 0, 104448, 0, 1, 0, 0),
    (8, 4, 4, 4, 139264, 1, 1, 0, 104448, 0, 1, 0, 0),
    (8, 4, 4, 4, 139264, 1, 1, 0, 104448, 0, 1, 0, 0),
]
EXPECTED["minicpm_sala"] = [
    (4, 4, 0, 96, 32768, 1, 24, 16384, 0, 1, 0, 0, 0),
    (16, 12, 0, 96, 49152, 3, 24, 49152, 0, 3, 0, 0, 0),
    (16, 12, 8, 104, 61440, 3, 26, 49152, 0, 3, 0, 0, 0),
    (16, 12, 8, 104, 73728, 3, 26, 49152, 0, 3, 0, 0, 0),
    (16, 12, 8, 36, 73728, 3, 9, 49152, 0, 3, 0, 6, 96),
    (8, 4, 4, 4, 36864, 1, 1, 16384, 0, 1, 0, 1, 16),
    (8, 4, 4, 4, 36864, 1, 1, 16384, 0, 1, 0, 1, 16),
    (8, 4, 4, 4, 36864, 1, 1, 16384, 0, 1, 0, 1, 16),
]
# no parent's: as PR 49 counted them (a state and a tail in and out a
# run and a delta layer: 2 x 3 x (4 x 16 x 16 x 4 + 3 x 128 x 4) a run)
EXPECTED["qwen3_next"] = [
    (1, 1, 0, 24, 131072, 1, 24, 33792, 0, 1, 0, 0, 0),
    (4, 3, 0, 24, 147456, 3, 24, 101376, 0, 3, 0, 0, 0),
    (4, 3, 2, 26, 159744, 3, 26, 101376, 0, 3, 0, 0, 0),
    (4, 3, 2, 26, 172032, 3, 26, 101376, 0, 3, 0, 0, 0),
    (4, 3, 2, 9, 176128, 3, 9, 101376, 0, 3, 0, 0, 0),
    (2, 1, 1, 1, 135168, 1, 1, 33792, 0, 1, 0, 0, 0),
    (2, 1, 1, 1, 135168, 1, 1, 33792, 0, 1, 0, 0, 0),
    (2, 1, 1, 1, 135168, 1, 1, 33792, 0, 1, 0, 0, 0),
]
# no parent's: as PR 58 counted them (one paged call a step, on the one
# attention layer; a tail in and out a run and a convolution layer: 2 x
# 5 x 2 x 32 x 4 B a run, and no state)
EXPECTED["lfm2_moe"] = [
    (1, 1, 0, 24, 32768, 1, 24, 2560, 0, 1, 0, 0, 0),
    (4, 3, 0, 24, 36864, 3, 24, 7680, 0, 3, 0, 0, 0),
    (4, 3, 0, 26, 39936, 3, 26, 7680, 0, 3, 0, 0, 0),
    (4, 3, 0, 26, 43008, 3, 26, 7680, 0, 3, 0, 0, 0),
    (4, 3, 0, 9, 44032, 3, 9, 7680, 0, 3, 0, 0, 0),
    (2, 1, 0, 1, 33792, 1, 1, 2560, 0, 1, 0, 0, 0),
    (2, 1, 0, 1, 33792, 1, 1, 2560, 0, 1, 0, 0, 0),
    (2, 1, 0, 1, 33792, 1, 1, 2560, 0, 1, 0, 0, 0),
]


@pytest.mark.parametrize("kind", list(BUILDS))
def test_the_host_s_counts_are_the_parent_s(kind):
    eng = _engine(kind, fresh=True)
    steps = _drive(eng, (30, 3, 70 if eng.max_positions > 100 else 40), 4)
    got = [tuple(int(getattr(ev, k)) for k in COUNTED) for ev in steps]
    assert got == EXPECTED[kind]
    g = eng.geometry
    assert g.counted == M.LIVE_COUNTS + (
        M.HYBRID_COUNTS if eng.cache_cfg.hybrid is not None else ()) + (
        M.SELECT_COUNTS if g.dense_pages else ()) + (
        M.DELTA_COUNTS if g.delta_impl is not None else ()) + (
        M.CONV_COUNTS if g.conv_layers else ())
    assert not set(M.EVENT_COUNTS) & set(g.counted)
    assert set(M.STEP_COUNTS) < set(E.StepEvents.__slots__)


# ------------------------------------------------ (d) the carried memory
def test_the_gated_memory_unit_reads_what_the_memory_layer_returned():
    """A Phi step's tokens equal the op graph's own (no cache, no
    carried value), through chunks and decode lanes; and the unit's
    body is a function of the memory it is handed."""
    eng = _engine("phi4flash")
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(1, eng.vocab_size, size=n))
               for n in (40, 5, 21)]
    out = eng.generate(prompts, 6)
    assert out == eng.generate_reference(prompts, 6)
    arch = eng.arch
    i = [arch.mixer(j) for j in range(arch.num_layers)].index(GMU)
    assert i > arch.memory_layer
    x = jnp.asarray(rng.randn(3, eng.hidden), jnp.float32)
    h = jnp.asarray(rng.randn(3, eng.hidden), jnp.float32)
    mem = jnp.asarray(rng.randn(3, arch.d_inner), jnp.float32)
    y, pool, back = M.BODIES[GMU](eng.geometry, eng.params, i, x, h, None,
                                  "pool", mem)
    assert pool == "pool" and back is mem
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(arch.gmu(eng.params, i, h, mem, x)))
    y2, _, _ = M.BODIES[GMU](eng.geometry, eng.params, i, x, h, None,
                             "pool", 2 * mem)
    assert float(jnp.abs(y2 - y).max()) > 0
