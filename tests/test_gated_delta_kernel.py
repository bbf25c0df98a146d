"""The delta rule's lanes as a Pallas kernel (kernels/gated_delta_scan.py,
PR 51), through the Pallas interpreter: against its jnp twin
`ops/gated_delta.py::segmented` and against the recurrence a token a
trip, over the kinds of run a serving step holds — decode lanes beside a
chunk, a run that goes lanes, chunk-form blocks, lanes, a run cut at any
lane of a step; the plan the kernel walks; the engine built on the
interpreted kernel against the reference; and what the record and the
`dispatch` span say of the forms.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from lib import qwen3next_cell  # noqa: E402

from test_qwen3_next import CONF as SMALL, INIT  # noqa: E402

from flexflow_tpu.config import CompMode, FFConfig  # noqa: E402
from flexflow_tpu.kernels import gated_delta_scan as K  # noqa: E402
from flexflow_tpu.models.qwen3_next import build_qwen3_next_lm  # noqa: E402
from flexflow_tpu.ops import gated_delta as GD  # noqa: E402
from flexflow_tpu.ops import ssm  # noqa: E402
from flexflow_tpu.serve import ServeEngine  # noqa: E402
from flexflow_tpu.serve import mixers  # noqa: E402
from flexflow_tpu.serve.engine import ServeSession  # noqa: E402
from flexflow_tpu.utils.telemetry import Telemetry  # noqa: E402

# the smallest shape the kernel takes: a key dimension of one tile
T, SLOTS, H, DK, DV, LAYERS, LAYER = 192, 8, 8, 128, 128, 3, 1
TOL = 1e-5


def _sequence(t, seed, h=H):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    return (unit(f(t, h, DK)) / np.sqrt(DK), unit(f(t, h, DK)), f(t, h, DV),
            -jnp.exp(f(t, h) - 2.0), jax.nn.sigmoid(f(t, h)))


def _slab(seed, h=H):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(
        (LAYERS, SLOTS + 1, h * DK, DV)), jnp.float32)


def _lanes(runs, t=T):
    """runs: (slot, first position, lanes) one after another from lane
    0; the lanes behind them are dead (slot 0, position 0, as _pack
    leaves them) -> the lane arrays as `step_lanes` makes them."""
    slots, pos = np.zeros(t, np.int32), np.zeros(t, np.int32)
    n = 0
    for slot, p0, k in runs:
        slots[n:n + k] = slot
        pos[n:n + k] = np.arange(p0, p0 + k)
        n += k
    live = jnp.arange(t) < n
    slots, pos = jnp.asarray(slots), jnp.asarray(pos)
    starts = ssm.run_starts(slots, pos)
    return (slots, pos, live, starts,
            ssm.run_write_slots(starts, live, slots, SLOTS), jnp.int32(n))


@jax.jit
def _twin(x, slab, lanes):
    return GD.segmented(*x, slab, *lanes, layer=LAYER)


@jax.jit
def _kernel(x, slab, lanes):
    slots, pos, live, starts, _, n = lanes
    plan = GD.lane_plan(slots, pos, live, starts, n)
    return K.gated_delta_scan(*x, slab, LAYER, slots, pos, plan,
                              interpret=True)


RUNS = {
    "decode lanes beside a chunk":
        [(0, 9, 1), (4, 100, 1), (7, 1, 1), (1, 33, 1), (5, 20, 64 + 40)],
    "lanes, chunk-form blocks, lanes":
        [(3, 40, 1), (2, 5, 1), (6, 11, 62 + 64 + 9), (0, 77, 1)],
    "two chunks that share a block":
        [(3, 0, 64 + 30), (6, 50, 34 + 64), (1, 8, 1)],
    "a sequence that starts at position 0 inside the step":
        [(2, 7, 9), (5, 0, 13)],
    "a tail of fifteen lanes": [(4, 640, 15)],
    "a block of sixteen lanes, the chunk form": [(4, 640, 16)],
    "a dead tail of lanes": [(3, 2, 11), (1, 40, 1)],
    "no live lane": [],
}
FORMS = {      # (lanes that go lane by lane, chunk-form blocks)
    "decode lanes beside a chunk": (4 + 60, 1),
    "lanes, chunk-form blocks, lanes": (2 + 62 + 9 + 1, 1),
    "two chunks that share a block": (30 + 34, 2),
    "a sequence that starts at position 0 inside the step": (22, 0),
    "a tail of fifteen lanes": (15, 0),
    "a block of sixteen lanes, the chunk form": (0, 1),
    "a dead tail of lanes": (12, 0),
    "no live lane": (0, 0),
}


@pytest.mark.parametrize("case", list(RUNS))
def test_kernel_equals_its_twin_and_the_recurrence(case):
    runs = RUNS[case]
    x, slab = _sequence(T, len(case)), _slab(len(case))
    lanes = _lanes(runs)
    n = int(lanes[-1])
    o0, s0 = map(np.asarray, _twin(x, slab, lanes))
    o1, s1 = map(np.asarray, _kernel(x, slab, lanes))
    # the live lanes' rows and every slot's state equal the twin's; no
    # other layer of the slab, and no slot that no run ended in, is
    # touched (the sink row is nobody's)
    np.testing.assert_allclose(o1[:n], o0[:n], atol=TOL, rtol=0)
    np.testing.assert_allclose(s1[LAYER, :SLOTS], s0[LAYER, :SLOTS],
                               atol=TOL, rtol=0)
    written = {slot for slot, _, _ in runs}
    for layer in range(LAYERS):
        for slot in range(SLOTS):
            if layer != LAYER or slot not in written:
                np.testing.assert_array_equal(s1[layer, slot],
                                              np.asarray(slab[layer, slot]))
    # each run against the recurrence from its slot's state
    lane = 0
    for slot, p0, k in runs:
        start = slab[LAYER, slot].reshape(H, DK, DV) if p0 else None
        sl = slice(lane, lane + k)
        o, s = GD.recurrent(*(a[sl] for a in x), state=start)
        np.testing.assert_allclose(o1[sl], np.asarray(o), atol=1e-4,
                                   rtol=1e-4)
        np.testing.assert_allclose(s1[LAYER, slot].reshape(H, DK, DV),
                                   np.asarray(s), atol=1e-4, rtol=1e-4)
        lane += k


@pytest.mark.parametrize("case", list(RUNS))
def test_the_plan_and_the_host_s_count_sort_the_lanes_alike(case):
    """`lane_plan` on the device and `step_counts`' rule on the host
    (the same `block_forms`, over numpy): the lanes that go lane by
    lane are the two passes' segments' lanes, each once."""
    slots, pos, live, starts, _, n = _lanes(RUNS[case])
    plan = GD.lane_plan(slots, pos, live, starts, n)
    as_chunk, count, _ = GD.block_forms(
        ssm.run_starts(np.asarray(slots), np.asarray(pos), np),
        np.asarray(live), int(n), np)
    assert (int(count[~as_chunk].sum()), int(as_chunk.sum())) == FORMS[case]
    chunks = int(plan.chunks)
    np.testing.assert_array_equal(np.asarray(plan.chunk_ids[:chunks]),
                                  np.flatnonzero(as_chunk))
    walked = np.zeros(T, int)
    for seg in (plan.before, plan.after):
        for i in range(int(seg.count)):
            first, length = int(seg.first[i]), int(seg.length[i])
            walked[first:first + length] += 1
            assert int(seg.dst[i]) == int(slots[first])
            assert int(seg.src[i]) == (int(slots[first]) if int(pos[first])
                                       else -1)
    np.testing.assert_array_equal(
        walked, (np.arange(T) < int(n)) & ~np.repeat(as_chunk, GD.CHUNK))
    assert walked.sum() == FORMS[case][0]


@pytest.mark.parametrize("cut", list(range(1, 40, 3)) + [64, 100, 149])
def test_a_run_split_at_any_lane_of_a_step_equals_the_recurrence(cut):
    """tests/test_qwen3_next.py's guard on the kernel: a sequence of 150
    tokens served as two steps cut at `cut` beside a decode lane, the
    second resuming from the slot's state whatever the cut — lanes into
    chunk-form blocks into lanes on both sides of it."""
    a, b = _sequence(150, 1), _sequence(2, 2)
    want = np.asarray(GD.recurrent(*a)[0])
    slab = jnp.full((LAYERS, SLOTS + 1, H * DK, DV), 7.0)

    def step(slab, first, n, other):
        x = tuple(jnp.concatenate([
            u[other:other + 1], v[first:first + n],
            jnp.zeros((T - n - 1,) + v.shape[1:])]) for u, v in zip(b, a))
        o, slab = _kernel(x, slab, _lanes([(5, other, 1), (2, first, n)]))
        return np.asarray(o[1:1 + n]), slab

    o1, slab = step(slab, 0, cut, 0)
    o2, slab = step(slab, cut, 150 - cut, 1)
    assert np.abs(np.concatenate([o1, o2]) - want).max() < TOL
    assert np.all(np.asarray(slab[LAYER, 1]) == 7.0)    # untouched slots
    assert np.all(np.asarray(slab[0]) == 7.0)           # ... and layers


def test_a_run_cut_by_the_step_s_end_resumes_the_next_step():
    """Two calls equal one: 100 lanes of slot 4 from position 6, then its
    next 60 — against the 160 in one step."""
    x, slab = _sequence(T, 7), _slab(7)
    o, out = _kernel(x, slab, _lanes([(4, 6, 160)]))
    o_a, mid = _kernel(x, slab, _lanes([(4, 6, 100)]))
    shift = lambda a: jnp.concatenate([a[100:], a[:100]])
    o_b, out2 = _kernel(tuple(map(shift, x)), mid, _lanes([(4, 106, 60)]))
    for got, want in ((o_a[:100], o[:100]), (o_b[:60], o[100:160]),
                      (out2[LAYER, 4], out[LAYER, 4])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=TOL, rtol=0)


def test_a_readmitted_slot_starts_from_zero():
    """Slot 0 holds a finished sequence's state; a sequence admitted to
    it at position 0 starts from zero, as one lane and as a chunk."""
    x, slab = _sequence(T, 3), _slab(3)
    for n in (1, 20, 70):
        o, out = _kernel(x, slab, _lanes([(3, 50, 1), (0, 0, n)]))
        want, s = GD.recurrent(*(a[1:1 + n] for a in x))
        np.testing.assert_allclose(np.asarray(o[1:1 + n]), np.asarray(want),
                                   atol=TOL, rtol=0)
        np.testing.assert_allclose(
            np.asarray(out[LAYER, 0]).reshape(H, DK, DV), np.asarray(s),
            atol=TOL, rtol=0)


def test_dead_lanes_write_only_the_sink():
    """Nothing live: every slot of every layer stays as it was."""
    x, slab = _sequence(T, 4), _slab(4)
    _, out = _kernel(x, slab, _lanes([]))
    np.testing.assert_array_equal(np.asarray(out[:, :SLOTS]),
                                  np.asarray(slab[:, :SLOTS]))


@pytest.mark.parametrize("heads", [8, 16])
def test_value_heads_are_independent(heads):
    """The first eight of sixteen heads give the bits eight alone give."""
    lanes = _lanes([(5, 3, 17), (0, 0, 6), (2, 9, 1)])
    wide, slab = _sequence(T, 3, 16), _slab(3, 16)
    o_ref, s_ref = _kernel(tuple(a[:, :8] for a in wide),
                           slab[:, :, :8 * DK], lanes)
    o, s = _kernel(tuple(a[:, :heads] for a in wide),
                   slab[:, :, :heads * DK], lanes)
    np.testing.assert_array_equal(np.asarray(o[:24, :8]),
                                  np.asarray(o_ref[:24]))
    np.testing.assert_array_equal(np.asarray(s[:, :SLOTS, :8 * DK]),
                                  np.asarray(s_ref[:, :SLOTS]))


@pytest.mark.parametrize("lanes,heads,dk,dv,ok", [
    (576, 32, 128, 128, True),      # Qwen3-Next, a quarter of a layer
    (576, 64, 128, 128, True),      # the whole layer: k and q fill the tile
    (576, 72, 128, 128, False),     # ... and would pass it
    (32, 8, 128, 128, True),        # this file's engine
    (28, 4, 16, 16, False),         # tests/test_qwen3_next.py's
    (576, 32, 64, 128, True),       # a key dimension under the tile (PR 52)
    (576, 32, 128, 64, True),       # two heads side by side fill the lanes
    (576, 12, 128, 128, True),      # q's heads at the next sublane tile
    (544, 30, 96, 192, True),       # Olmo-Hybrid: all three at once
    (544, 30, 100, 192, False),     # a key dimension off the sublanes
    (544, 30, 96, 96, False),       # pairs of 192 lanes fill no tile
    (544, 15, 96, 192, False),      # an odd head count has no pairs
    (576, 32, 256, 128, False),     # a key dimension past the tile
    (4096, 32, 128, 128, False),    # exp(g) and beta past SMEM
])
def test_what_the_kernel_takes(lanes, heads, dk, dv, ok):
    assert K.supported(lanes, heads, dk, dv) is ok


# ------------------------------------------------- the engine on the kernel
CONF = dict(SMALL, linear_num_key_heads=4, linear_num_value_heads=8)


def _lm(**cfg):
    """tests/test_qwen3_next.py's small model at a delta layer the
    kernel takes: 4 key / 8 value heads of 128 x 128."""
    base = dict(batch_size=1, seed=5, kv_page_size=8, kv_num_pages=129,
                serve_max_seqs=4, serve_prefill_budget=76,
                serve_spec_decode=False, serve_prefix_cache=False)
    base.update(cfg)
    lm = build_qwen3_next_lm(
        FFConfig(**base), vocab_size=CONF["vocab_size"], max_seq_len=256,
        hidden=CONF["hidden_size"], num_layers=4, num_heads=8,
        num_kv_heads=2, head_dim=CONF["head_dim"], key_heads=4,
        value_heads=8, key_dim=128, value_dim=128, num_experts=16,
        experts_per_token=CONF["num_experts_per_tok"], expert_dim=32,
        shared_expert_dim=32, experts_held=(4, 8), norm_init=INIT["norm"],
        qk_norm_init=INIT["qk_norm"], delta_norm_init=INIT["delta_norm"],
        dt_range=INIT["dt"])
    lm.compile(comp_mode=CompMode.INFERENCE)
    return lm


def _tokens(n, seed):
    return np.random.default_rng(seed).integers(
        1, CONF["vocab_size"], n).tolist()


@pytest.fixture(scope="module")
def telemetry():
    return Telemetry()


@pytest.fixture(scope="module")
def engine(telemetry):
    eng = ServeEngine(_lm(), interpret=True, telemetry=telemetry)
    eng.warmup()
    return eng


def test_engine_on_the_interpreted_kernel_equals_the_reference(engine):
    """Two prompts' chunks beside decode lanes, a block of the chunk
    form among them (80 lanes a step), the delta rule through the
    kernel."""
    assert engine.geometry.delta_impl == "pallas_interpret"
    groups = [[_tokens(9, 13)], [_tokens(170, 14), _tokens(33, 15)]]
    rows, stats = qwen3next_cell.logits_through_cache(engine, CONF, groups,
                                                      8)
    for r in rows:
        assert r["new"] == 8
        assert r["logit_abs_err"] < 2e-4, r
        assert r["worst_gap"] < 2e-4, r
    assert max(r["prefill_chunks"] for r in rows) >= 2
    assert stats["nonfinite_logit_steps"] == 0
    assert engine.compile_counts()["mixed"] == 1
    engine.cache.check_invariants(engine.pool)


@pytest.mark.parametrize("where", ["boot_stats", "fingerprint",
                                   "last_stats"])
def test_the_record_says_which_form_ran(engine, where):
    if where == "last_stats":
        engine.generate([_tokens(5, 1)], max_new_tokens=2)
    rec = {"boot_stats": lambda: engine.boot_stats,
           "fingerprint": engine._program_fingerprint,
           "last_stats": lambda: engine.last_stats}[where]()
    assert rec["delta_impl"] == "pallas_interpret"


@pytest.mark.parametrize("kw,impl", [
    (dict(interpret=True), "pallas_interpret"),     # 28 lanes of 8 heads
    (dict(), "jnp"),                                # a CPU engine
])
def test_the_shape_and_the_engine_s_attention_decide_the_form(kw, impl):
    eng = ServeEngine(_lm(serve_prefill_budget=24), **kw)
    assert eng.geometry.delta_impl == impl
    # no plan is made for the twin, which sorts its own lanes
    lane = jnp.zeros((eng.mixed_width,), jnp.int32)
    made = mixers.step_lanes(
        eng.geometry, lane, lane, lane,
        jnp.zeros((4, eng.cache_cfg.pages_per_seq), jnp.int32), lane, lane)
    assert (made.delta_plan is not None) == (impl != "jnp")
    eng.close()


def test_the_step_and_the_dispatch_span_carry_the_two_counts(engine,
                                                             telemetry):
    """A prompt of 170 in steps of 76 prefill lanes, then another
    beside its decode lane: a whole block of one run takes the chunk
    form, the rest goes lane by lane; the span's arguments are the
    step's counts."""
    assert mixers.DELTA_COUNTS == ("delta_lanes", "delta_chunk_blocks")
    assert set(mixers.DELTA_COUNTS) <= set(engine.geometry.counted)
    telemetry.events.clear()
    steps = []
    with ServeSession(engine) as s:
        s.submit(_tokens(170, 22), 8)
        for _ in range(2):
            ev = s.step()
            if ev is not None and ev.dispatched:
                steps.append(ev)
        s.submit(_tokens(5, 21), 3)
        while s.has_work():
            ev = s.step()
            if ev is not None and ev.dispatched:
                steps.append(ev)
    steps.sort(key=lambda ev: ev.step_index)
    for ev in steps:
        live = sum(ch.end - ch.start for ch in ev.plan.chunks)
        blocks = ev.delta_chunk_blocks
        assert ev.delta_lanes + 64 * blocks >= live >= ev.delta_lanes
        assert ev.delta_lanes <= live - 16 * blocks
    assert any(ev.delta_chunk_blocks for ev in steps)
    assert any(ev.delta_lanes == 1 for ev in steps)     # a decode lane
    spans = [e[6] for e in telemetry.events
             if e[0] == "X" and e[2] == "dispatch"]
    assert [(a["delta_lanes"], a["delta_chunk_blocks"]) for a in spans] \
        == [(ev.delta_lanes, ev.delta_chunk_blocks) for ev in steps]
