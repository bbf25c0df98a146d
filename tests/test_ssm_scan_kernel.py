"""The serving scan as a Pallas kernel (kernels/ssm_scan.py, PR 33),
through the Pallas interpreter: against its jnp twin
`ops/ssm.py::segmented_scan` and against a plain per-sequence f32
recurrence, over the kinds of run a serving step holds; the engine built
on the interpreted kernel against the reference; and the two facts the
kernel leans on — `_pack` leaves the live lanes a prefix, and the
engine's record says which scan ran.
"""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from lib import phi4flash_cell  # noqa: E402

from test_phi4flash import CONF, _lm, _tokens  # noqa: E402

from flexflow_tpu.kernels import ssm_scan as K  # noqa: E402
from flexflow_tpu.ops import ssm  # noqa: E402
from flexflow_tpu.serve import ServeEngine  # noqa: E402
from flexflow_tpu.serve.engine import ServeSession  # noqa: E402

T, SLOTS, N, D, LAYERS, LAYER = 40, 8, 16, 256, 3, 1
TOL = 1e-5


def _inputs(seed, t=T, d=D):
    r = np.random.default_rng(seed)
    f = lambda a: jnp.asarray(a, jnp.float32)
    p = {"A_log": f(np.log(np.arange(1, N + 1))[:, None]
                    + 0.1 * r.standard_normal((N, d))),
         "D": f(r.standard_normal(d))}
    u = f(r.standard_normal((t, d)))
    dt = f(np.exp(r.uniform(np.log(1e-3), np.log(1e-1), (t, d))))
    b, c = f(r.standard_normal((t, N))), f(r.standard_normal((t, N)))
    slab = f(r.standard_normal((LAYERS, SLOTS + 1, N, d)))
    return p, u, dt, b, c, slab


def _lanes(runs, t=T):
    """runs: (slot, first position, lanes) one after another from lane
    0; the lanes behind them are dead (slot 0, position 0, as _pack
    leaves them). -> the lane arrays as serve/mixers.py
    `step_lanes` makes them."""
    slots, pos = np.zeros(t, np.int32), np.zeros(t, np.int32)
    n = 0
    for slot, p0, k in runs:
        slots[n:n + k] = slot
        pos[n:n + k] = np.arange(p0, p0 + k)
        n += k
    live = jnp.arange(t) < n
    slots, pos = jnp.asarray(slots), jnp.asarray(pos)
    starts = ssm.run_starts(slots, pos)
    return (slots, pos, starts,
            ssm.run_write_slots(starts, live, slots, SLOTS), n)


def _plain(p, u, dt, b, c, s):
    """One sequence's recurrence, lane by lane: s (N, d) -> (y, s)."""
    a = -np.exp(np.asarray(p["A_log"], np.float64))
    ys = []
    for t in range(u.shape[0]):
        dtt, ut = np.asarray(dt[t], np.float64), np.asarray(u[t], np.float64)
        s = np.exp(dtt[None] * a) * s + (dtt * ut)[None] \
            * np.asarray(b[t], np.float64)[:, None]
        ys.append((s * np.asarray(c[t], np.float64)[:, None]).sum(0)
                  + np.asarray(p["D"], np.float64) * ut)
    return np.stack(ys), s


def _both(args, lanes, block=128):
    p, u, dt, b, c, slab = args
    slots, pos, starts, wslots, n = lanes
    y0, row = ssm.segmented_scan(p, u, dt, b, c, slab[LAYER], slots, pos,
                                 starts, wslots)
    y1, out = K.ssm_scan(p, u, dt, b, c, slab, LAYER, slots, pos, starts,
                         wslots, n, block=block, interpret=True)
    return (np.asarray(y0), np.asarray(row)), (np.asarray(y1),
                                               np.asarray(out))


RUNS = {
    "a chunk that continues a slot's state": [(3, 5, 24)],
    "a sequence that starts at position 0 inside the step":
        [(2, 7, 9), (5, 0, 13)],
    "decode runs of one lane": [(0, 9), (4, 100), (7, 1), (1, 33)],
    "a chunk beside decode lanes, the step full":
        [(6, 16, 31), (0, 3, 1), (2, 50, 1)] + [(s, 8, 1) for s in
                                                (1, 3, 4, 5)] + [(7, 0, 3)],
    "a dead tail of lanes": [(3, 2, 11), (1, 40, 1)],
    "no live lane": [],
}


@pytest.mark.parametrize("case", list(RUNS))
def test_kernel_equals_its_twin_and_the_plain_recurrence(case):
    runs = [r if len(r) == 3 else r + (1,) for r in RUNS[case]]
    args = _inputs(len(case))
    p, u, dt, b, c, slab = args
    lanes = _lanes(runs)
    n = lanes[-1]
    (y0, row0), (y1, out) = _both(args, lanes)
    # the live lanes' rows and every slot's state equal the twin's; the
    # dead lanes' rows are zeros; no other layer of the slab, and no
    # slot that no run ended in, is touched (the sink row is nobody's)
    np.testing.assert_allclose(y1[:n], y0[:n], atol=TOL, rtol=0)
    assert not y1[n:].any()
    np.testing.assert_allclose(out[LAYER, :SLOTS], row0[:SLOTS], atol=TOL,
                               rtol=0)
    written = {slot for slot, _, _ in runs}
    for layer in range(LAYERS):
        for slot in range(SLOTS):
            if layer != LAYER or slot not in written:
                np.testing.assert_array_equal(out[layer, slot],
                                              np.asarray(slab[layer, slot]))
    # each run against the plain recurrence from its slot's state
    lane = 0
    for slot, p0, k in runs:
        s0 = np.asarray(slab[LAYER, slot], np.float64) if p0 else \
            np.zeros((N, D))
        sl = slice(lane, lane + k)
        y, s = _plain(p, u[sl], dt[sl], b[sl], c[sl], s0)
        np.testing.assert_allclose(y1[sl], y, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(out[LAYER, slot], s, atol=1e-4,
                                   rtol=1e-4)
        lane += k


def test_a_run_cut_by_the_step_s_end_resumes_the_next_step():
    """Two calls equal one: 20 lanes of slot 4 from position 6, then its
    next 12 — against the 32 in one step."""
    p, u, dt, b, c, slab = _inputs(7)
    whole = _lanes([(4, 6, 32)])
    y, out = K.ssm_scan(p, u, dt, b, c, slab, LAYER, *whole, block=128,
                        interpret=True)
    first, second = _lanes([(4, 6, 20)]), _lanes([(4, 26, 12)])
    y_a, mid = K.ssm_scan(p, u, dt, b, c, slab, LAYER, *first, block=128,
                          interpret=True)
    shift = lambda x: jnp.concatenate([x[20:], x[:20]])
    y_b, out2 = K.ssm_scan(p, shift(u), shift(dt), shift(b), shift(c), mid,
                           LAYER, *second, block=128, interpret=True)
    # (to rounding: the interpreter's XLA contracts a lane's multiply-add
    # by its place in the trip, and the cut moves the places)
    for got, want in ((y_a[:20], y[:20]), (y_b[:12], y[20:32]),
                      (out2[LAYER, 4], out[LAYER, 4])):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("block", [128, 256])
def test_blocks_of_d_inner_are_independent(block):
    """One block of 256 and two of 128 give the same bits."""
    args = _inputs(3)
    lanes = _lanes([(5, 3, 17), (0, 0, 6), (2, 9, 1)])
    _, (y_ref, out_ref) = _both(args, lanes, block=256)
    _, (y, out) = _both(args, lanes, block=block)
    np.testing.assert_array_equal(y, y_ref)
    np.testing.assert_array_equal(out[:, :SLOTS], out_ref[:, :SLOTS])


@pytest.mark.parametrize("lanes,d_state,d_inner,ok", [
    (576, 16, 5120, True),       # Phi-4-mini-flash, served
    (40, 16, 128, True),         # its rehearsal size
    (28, 16, 128, False),        # lanes off the sublane tile
    (576, 12, 5120, False),      # d_state off it
    (576, 16, 192, False),       # d_inner off the lane tile
])
def test_what_the_kernel_takes(lanes, d_state, d_inner, ok):
    assert K.supported(lanes, d_state, d_inner) is ok
    if ok:
        assert d_inner % K.choose_block(d_inner) == 0
        assert K.choose_block(d_inner) <= K.MAX_BLOCK


# ------------------------------------------------- the engine on the kernel
def _engine(budget, **kwargs):
    """tests/test_phi4flash.py's small model at another prefill budget
    (4 slots: budget + 4 lanes a step)."""
    return ServeEngine(_lm(serve_prefill_budget=budget), **kwargs)


@pytest.fixture(scope="module")
def engine():
    eng = _engine(28, interpret=True)        # 32 lanes: the kernel's shape
    eng.warmup()
    return eng


def test_engine_on_the_interpreted_kernel_equals_the_reference(engine):
    """tests/test_phi4flash.py's case of two prompts' chunks beside
    decode lanes, the scans through the kernel."""
    assert engine.scan_impl == "pallas_interpret"
    groups = [[_tokens(9, 13)], [_tokens(40, 14), _tokens(33, 15)]]
    rows, stats = phi4flash_cell.logits_through_cache(engine, CONF, groups,
                                                      10)
    for r in rows:
        assert r["new"] == 10
        assert r["logit_abs_err"] < 1e-4, r
        assert r["worst_gap"] < 1e-4, r
    assert max(r["prefill_chunks"] for r in rows) >= 2
    assert stats["nonfinite_logit_steps"] == 0
    assert engine.compile_counts()["mixed"] == 1
    engine.cache.check_invariants(engine.pool)


def test_the_record_says_which_scan_ran(engine):
    assert engine.boot_stats["scan_impl"] == "pallas_interpret"
    assert engine._program_fingerprint()["scan_impl"] == "pallas_interpret"
    # 28 lanes are off the sublane tile: the twin, under the same
    # interpreted paged kernel; and the twin wherever jnp attention runs
    assert _engine(24, interpret=True).scan_impl == "jnp"
    assert _engine(28).scan_impl == "jnp"


def test_pack_leaves_the_live_lanes_a_prefix(engine, monkeypatch):
    """What the kernel's trip count leans on: every plan's live lanes
    (write page not the sink) are lanes 0 .. live - 1."""
    plans = []
    pack = ServeSession._pack

    def spy(self, plan):
        out = pack(self, plan)
        plans.append((np.asarray(out[0][2]), out[2]))
        return out

    monkeypatch.setattr(ServeSession, "_pack", spy)
    stats = engine.generate(
        [_tokens(50, 21), _tokens(7, 22), _tokens(30, 23)],
        max_new_tokens=6)
    assert engine.last_stats["scan_impl"] == "pallas_interpret", stats
    assert len(plans) >= 6
    for write_pages, live in plans:
        assert (write_pages[:live] != 0).all() and not write_pages[live:].any()
