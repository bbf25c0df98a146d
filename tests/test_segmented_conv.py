"""The serving step's convolution on its own (ops/ssm.py::segmented_conv,
PR 53): whole sequences through `causal_conv` against the same sequences
cut into steps and fed through `segmented_conv` with the lane arrays
`serve/mixers.py::step_lanes` makes — and, step by step, against the
form the function had until PR 53, kept here as the plain reference: a
scatter of EVERY lane's row, all but a run's last live one aimed at a
sink row. `y` and every slot's tail have to come out the same bits.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops import ssm

F32 = jnp.float32
D_INNER, D_CONV = 8, 4


def scatter_form(p, u, tail, lane_slots, positions, offsets, wslots):
    """`segmented_conv` as it stood before PR 53: the tail's write-back
    scatters all T lanes' rows, `wslots` aiming every lane but a run's
    last live one at the sink row."""
    w = p["conv_w"].astype(F32)
    k = w.shape[0]
    t = u.shape[0]
    old = jnp.take(tail, lane_slots, axis=0).reshape(t, k - 1, -1)
    run_pos0 = positions - offsets
    old = jnp.where((run_pos0 > 0)[:, None, None], old, 0)
    hist = []
    for j in range(k - 1, 0, -1):
        shifted = jnp.concatenate(
            [jnp.zeros((j,) + u.shape[1:], u.dtype), u[:t - j]])
        idx = jnp.clip(k - 1 + offsets - j, 0, k - 2)
        from_tail = jnp.take_along_axis(
            old, idx[:, None, None], axis=1)[:, 0]
        hist.append(jnp.where((offsets >= j)[:, None], shifted, from_tail))
    y = u.astype(F32) * w[k - 1]
    if "conv_b" in p:
        y = y + p["conv_b"].astype(F32)
    for j, h in zip(range(k - 1, 0, -1), hist):
        y = y + h.astype(F32) * w[k - 1 - j]
    new = jnp.concatenate(hist[1:] + [u], axis=1).astype(tail.dtype)
    return y, tail.at[wslots].set(new)


def lane_arrays(runs, t, slots):
    """runs: (slot, first position, lanes) one after another from lane
    0, the lanes behind them dead on slot 0 at position 0, as `_pack`
    lays a plan. -> what `step_lanes` makes of them: (lane_slots,
    positions, offsets, wslots, tail_lanes) and the live lanes' count."""
    lane_slots, pos = np.zeros(t, np.int32), np.zeros(t, np.int32)
    n = 0
    for slot, p0, k in runs:
        lane_slots[n:n + k] = slot
        pos[n:n + k] = np.arange(p0, p0 + k)
        n += k
    assert n <= t
    live = jnp.arange(t) < n
    lane_slots, pos = jnp.asarray(lane_slots), jnp.asarray(pos)
    starts = ssm.run_starts(lane_slots, pos)
    wslots = ssm.run_write_slots(starts, live, lane_slots, slots)
    return (lane_slots, pos, ssm.run_offsets(starts), wslots,
            ssm.run_tail_lanes(wslots, slots)), n


def tail_lanes_by_hand(wslots, slots):
    out = np.full(slots, -1, np.int32)
    for lane, slot in enumerate(np.asarray(wslots)):
        if slot < slots:
            out[slot] = lane            # the later lane stays
    return out


_NEW = jax.jit(ssm.segmented_conv)
_OLD = jax.jit(scatter_form)


def _params(conv_b):
    key = jax.random.key(7)
    p = {"conv_w": jax.random.normal(key, (D_CONV, D_INNER), jnp.bfloat16)}
    if conv_b:
        p["conv_b"] = jax.random.normal(jax.random.key(8), (D_INNER,),
                                        jnp.bfloat16)
    return p


# A schedule is a list of steps, a step a list of (sequence, tokens):
# the sequence's next `tokens` inputs, in consecutive lanes. A sequence
# is (slot, length); two sequences may name one slot where the first is
# through before the second starts (a slot freed and used again).
def _decode_runs(slots):
    seqs = [(s, 6) for s in range(min(slots, 5))]
    return seqs, [[(i, 1) for i in range(len(seqs))] for _ in range(6)]


def _chunk_beside_decode(slots):
    seqs = [(1, 9), (2, 9), (slots - 1, 14)]
    steps = [[(0, 1), (1, 1)] for _ in range(3)]
    steps += [[(0, 1), (1, 1), (2, 7)], [(0, 1), (2, 7), (1, 1)]]
    return seqs, steps + [[(0, 1), (1, 1)] for _ in range(4)]


def _short_first_run(slots):
    # runs of 1 and 2 lanes that start their sequences: fewer than the
    # d_conv - 1 inputs a tail holds, the rest zeros
    seqs = [(0, 7), (3, 8)]
    return seqs, [[(0, 2), (1, 1)], [(1, 2), (0, 1)], [(0, 4), (1, 5)]]


def _resumed_chunks(slots):
    # a prompt in chunks of 5, 1, 2 and 6: each resumes at a position > 0
    seqs = [(2, 14), (0, 3)]
    return seqs, [[(0, 5)], [(1, 1), (0, 1)], [(0, 2), (1, 2)], [(0, 6)]]


def _dead_lanes_behind(slots):
    # one live lane of the step's many, then nothing live at all
    seqs = [(slots - 1, 5)]
    return seqs, [[(0, 2)], [], [(0, 1)], [], [(0, 2)]]


def _slot_reused(slots):
    # sequence 1 takes slot 1 after sequence 0 left its tail there: it
    # must read zeros, whatever lies in the slot
    seqs = [(1, 6), (1, 5), (0, 8)]
    return seqs, [[(0, 4), (2, 2)], [(0, 2), (2, 2)], [(2, 1), (1, 1)],
                  [(1, 2), (2, 1)], [(2, 2), (1, 2)]]


def _random(seed):
    def make(slots):
        r = np.random.default_rng(seed)
        seqs, steps, left, free = [], [], [], list(range(slots))
        while left or len(steps) < 14:
            while free and len(left) < 6 and len(steps) < 14 \
                    and r.random() < 0.6:
                slot = free.pop(int(r.integers(len(free))))
                seqs.append((slot, int(r.integers(1, 12))))
                left.append([len(seqs) - 1, seqs[-1][1]])
            step, room = [], 12
            for item in list(left):
                if r.random() < 0.25 or not room:
                    continue
                n = min(int(r.integers(1, 9)), item[1], room)
                step.append((item[0], n))
                item[1] -= n
                room -= n
                if not item[1]:
                    left.remove(item)
                    free.append(seqs[item[0]][0])
            r.shuffle(step)
            steps.append([tuple(s) for s in step])
        return seqs, steps
    return make


SCHEDULES = {
    "one_lane_decode_runs": _decode_runs,
    "a_chunk_beside_decode_lanes": _chunk_beside_decode,
    "a_first_run_shorter_than_the_tail": _short_first_run,
    "runs_resumed_past_position_0": _resumed_chunks,
    "dead_lanes_behind_the_live": _dead_lanes_behind,
    "a_slot_freed_and_used_again": _slot_reused,
    "random_0": _random(0), "random_1": _random(1), "random_2": _random(2),
}


@pytest.mark.parametrize("schedule", list(SCHEDULES))
@pytest.mark.parametrize("slots", [4, 32])
@pytest.mark.parametrize("conv_b", [True, False], ids=["bias", "no_bias"])
def test_steps_match_whole_sequences_and_the_scatter_form(conv_b, slots,
                                                          schedule):
    t = 16
    p = _params(conv_b)
    seqs, steps = SCHEDULES[schedule](slots)
    r = np.random.default_rng(len(schedule) + slots)
    data = [jnp.asarray(r.standard_normal((n, D_INNER)), jnp.bfloat16)
            for _, n in seqs]
    # every slot starts with something in it, the sink row too
    tail = jnp.asarray(r.standard_normal((slots + 1, (D_CONV - 1) * D_INNER)),
                       jnp.bfloat16)
    tail_old = tail
    done = [0] * len(seqs)
    got = [[] for _ in seqs]
    for step in steps:
        runs = [(seqs[i][0], done[i], n) for i, n in step]
        (lane_slots, pos, offsets, wslots, tail_lanes), live = lane_arrays(
            runs, t, slots)
        np.testing.assert_array_equal(
            np.asarray(tail_lanes), tail_lanes_by_hand(wslots, slots))
        rows = [data[i][done[i]:done[i] + n] for i, n in step]
        u = jnp.concatenate(rows + [jnp.asarray(
            r.standard_normal((t - live, D_INNER)), jnp.bfloat16)])
        y, tail = _NEW(p, u, tail, lane_slots, pos, offsets, tail_lanes)
        y_old, tail_old = _OLD(p, u, tail_old, lane_slots, pos, offsets,
                               wslots)
        # the same bits: every lane's y, every slot's tail (the sink
        # row, which nothing reads, is the scatter form's to write)
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y_old))
        np.testing.assert_array_equal(np.asarray(tail[:slots], np.float32),
                                      np.asarray(tail_old[:slots],
                                                 np.float32))
        lane = 0
        for i, n in step:
            got[i].append(np.asarray(y[lane:lane + n]))
            done[i] += n
            lane += n
    assert all(d == n for d, (_, n) in zip(done, seqs)), "a schedule's fault"
    for i, x in enumerate(data):
        whole = np.asarray(ssm.causal_conv(p, x[None])[0])
        np.testing.assert_allclose(np.concatenate(got[i]), whole,
                                   atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("slots", [4, 32])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_tail_lanes_against_a_loop_over_the_lanes(slots, seed):
    """`run_tail_lanes` of random plans, and of lists no plan makes: a
    slot named twice keeps the later lane, as a scatter applied in lane
    order would."""
    r = np.random.default_rng(seed)
    t = 48
    runs, n, free = [], 0, list(range(slots))
    while free and n < 40:
        slot = free.pop(int(r.integers(len(free))))
        k = int(r.integers(1, 9))
        runs.append((slot, int(r.integers(0, 50)), k))
        n += k
    (_, _, _, wslots, tail_lanes), live = lane_arrays(runs, t, slots)
    want = tail_lanes_by_hand(wslots, slots)
    np.testing.assert_array_equal(np.asarray(tail_lanes), want)
    assert (want >= 0).sum() == len(runs) and want.max() == live - 1
    twice = jnp.asarray(r.integers(0, slots + 1, (t,)), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(ssm.run_tail_lanes(twice, slots)),
        tail_lanes_by_hand(twice, slots))


def test_the_sink_row_is_left_as_it_lies():
    slots, t = 4, 16
    (lane_slots, pos, offsets, _, tail_lanes), _ = lane_arrays(
        [(2, 0, 5), (0, 9, 1)], t, slots)
    tail = jnp.arange((slots + 1) * (D_CONV - 1) * D_INNER, dtype=F32).reshape(
        slots + 1, -1).astype(jnp.bfloat16)
    u = jnp.ones((t, D_INNER), jnp.bfloat16)
    _, out = _NEW(_params(False), u, tail, lane_slots, pos, offsets,
                  tail_lanes)
    assert out.shape == tail.shape and out.dtype == tail.dtype
    same = np.asarray(out, np.float32) == np.asarray(tail, np.float32)
    assert same[[1, 3, 4]].all() and not same[2].all()
