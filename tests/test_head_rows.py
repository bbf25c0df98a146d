"""The mixed step's head runs over the rows of the lanes that EMIT
(`ServeEngine.head_rows` of them, gathered after the last layer and
before the head), not over the step's width: every fetched row is, bit
for bit on f32, the emitting lane's row of the all-lane head + top-k,
which the test computes itself from the same final activations — on a
mixed chunk + decode step, a chunk that ends mid-prompt, a speculative
step (1 + k rows side by side), a two-device head-sharded engine
(tests/test_serve_shard.py's) and the tiny OLMoE and Phi-4-flash engines
(tests/test_olmoe.py's, tests/test_phi4flash.py's). `head_rows` follows the
engine's shapes; `_pack` refuses a plan over it.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_olmoe import _lm as _olmoe_lm
from test_phi4flash import _lm as _phi_lm
from test_serve_shard import _lm as _shard_lm

from flexflow_tpu.config import CompMode, FFConfig
from flexflow_tpu.models.transformer import build_transformer_lm
from flexflow_tpu.serve import ServeEngine
from flexflow_tpu.serve import engine as E

VOCAB = 89


def _opt(max_seqs=4, budget=16, spec=False):
    cfg = FFConfig(batch_size=1, seed=3, kv_page_size=4, kv_num_pages=97,
                   serve_max_seqs=max_seqs, serve_prefill_budget=budget,
                   serve_spec_decode=spec, serve_prefix_cache=False)
    lm = build_transformer_lm(cfg, vocab_size=VOCAB, max_seq_len=96,
                              hidden=32, num_heads=4, num_layers=2,
                              ff_dim=64)
    lm.compile(comp_mode=CompMode.INFERENCE)
    return lm


def _echo(lm):
    """tests/test_speculative.py's repetitive generator: greedy decode
    echoes the trailing token, so the prompt-lookup drafts hold."""
    p = lm.state.params
    for i in range(2):
        for name, keys in ((f"layer{i}_attn", ("wo", "bo")),
                           (f"layer{i}_ff2", ("kernel", "bias"))):
            for key in keys:
                p[name][key] = jnp.zeros_like(p[name][key])
    p["pos_embed"]["kernel"] = p["pos_embed"]["kernel"] * 0.15
    p["lm_head"]["kernel"] = 4.0 * p["tok_embed"]["kernel"].T
    p["lm_head"]["bias"] = jnp.zeros_like(p["lm_head"]["bias"])
    return lm


def _prompts(sizes, seed=0):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(1, VOCAB, size=n)) for n in sizes]


# case -> (engine, prompts, new tokens, the step the case is about)
CASES = {
    "chunk_and_decode": (
        lambda: ServeEngine(_opt()), _prompts((5, 9, 30, 7)), 6,
        lambda ev, lanes: ev.plan.num_prefill_lanes
        and ev.plan.num_decode_lanes and ev.emitters),
    "mid_prompt_chunk": (
        lambda: ServeEngine(_opt()), _prompts((40,)), 3,
        lambda ev, lanes: ev.emitters == 0 and not lanes),
    "speculative": (
        lambda: ServeEngine(_echo(_opt(budget=32, spec=True)),
                            spec_tokens=5),
        [p * 3 for p in _prompts((4, 5, 3), seed=3)], 24,
        lambda ev, lanes: any(n > 2 for _, n in ev.emitted)),
    "head_sharded": (    # a vocabulary of 61, padded to the two devices
        lambda: ServeEngine(_shard_lm(spec=False), tensor_parallel=2),
        [[t % 60 + 1 for t in p] for p in _prompts((5, 9, 30, 7))], 6,
        lambda ev, lanes: ev.plan.num_prefill_lanes and ev.emitters),
    "olmoe": (
        lambda: ServeEngine(_olmoe_lm(), interpret=True),
        _prompts((21, 5, 40)), 4, lambda ev, lanes: ev.emitters > 1),
    "phi4flash": (
        lambda: ServeEngine(_phi_lm(), interpret=True),
        _prompts((21, 5, 40)), 4, lambda ev, lanes: ev.emitters > 1),
}


def _emitting_lanes(plan):
    """(lane, request id) of every lane whose logits the host reads,
    from the plan alone: an emitting chunk's last lane, and a
    speculative chunk's draft lanes after it — the emitters' first, as
    `_pack` lays the rows out."""
    lane, plain, spec = 0, [], []
    for ch in plan.chunks:
        lane += ch.end - ch.start
        if ch.draft_tokens:
            spec += [(ln, ch.req.rid) for ln in range(
                lane - 1, lane + len(ch.draft_tokens))]
            lane += len(ch.draft_tokens)
        elif ch.emits:
            plain.append((lane - 1, ch.req.rid))
    return plain + spec


def _tail(eng, params, x):
    """The head the engine's description computes over whatever rows it
    is handed, the sort and the argmax: the step's tail, unsharded."""
    logits = eng.arch.head(params, x)
    topv, topi = jax.lax.top_k(logits, eng.topk_cap)
    return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
            topv.astype(jnp.float32), topi.astype(jnp.int32))


@pytest.mark.parametrize("case", list(CASES))
def test_the_step_s_rows_are_the_emitting_lanes_of_the_all_lane_head(
        case, monkeypatch):
    make, prompts, new, about = CASES[case]
    eng = make()
    eng.warmup()
    assert eng.act_dtype == jnp.float32
    width, rows, k = eng.mixed_width, eng.head_rows, eng.topk_cap
    assert rows < width
    # the step's program once more over ALL lanes (nothing donated, the
    # pool as it is), its head recording the activations it is handed
    owner, name = (eng, "_head_tp") if eng.tp > 1 else (eng.arch, "head")
    head, seen = getattr(owner, name), []

    def recording(params, x, *axis):
        jax.debug.callback(lambda a: seen.append(np.asarray(a)), x)
        return head(params, x, *axis)

    all_lanes = jax.jit(lambda *a: eng._mixed_impl(*a)[:3])
    tail = jax.jit(lambda params, x: _tail(eng, params, x))
    packed, steps = [], []
    real_pack, real_dispatch = E.ServeSession._pack, eng._dispatch_mixed

    def pack(self, plan):
        want = _emitting_lanes(plan)
        out = real_pack(self, plan)
        packed.append((want, out[0][7]))
        return out

    def dispatch(*args, **kw):
        del seen[:]
        with monkeypatch.context() as m:
            m.setattr(owner, name, recording)
            # (a token the step before emits comes from its `greedy`,
            # still on the device: the step's own token source)
            full = all_lanes(eng._step_params, eng._device_pool(),
                             *args[:7], jnp.arange(width, dtype=jnp.int32),
                             args[8], eng._greedy)
            jax.effects_barrier()
        out = real_dispatch(*args, **kw)
        steps.append((seen[0], [np.asarray(a) for a in full],
                      [np.asarray(a) for a in out[:3]]))
        return out

    monkeypatch.setattr(E.ServeSession, "_pack", pack)
    monkeypatch.setattr(eng, "_dispatch_mixed", dispatch)
    hit = 0
    with eng.start_session() as s:
        for p in prompts:
            s.submit(p, new)
        while s.has_work():
            ev = s.step()
            if ev is None or not ev.dispatched:
                continue
            # a call hands out the step that LANDED in it, not the one
            # it packed
            want, head_lanes = packed[ev.step_index]
            lanes = [ln for ln, _ in want]
            x, full, got = steps[ev.step_index]
            assert ev.lanes == rows and ev.emitters <= ev.lanes
            assert head_lanes.shape == (rows,)
            assert list(head_lanes[:len(lanes)]) == lanes
            assert not head_lanes[len(lanes):].any()     # lane 0's
            assert x.shape == (width, eng.hidden)
            # the all-lane head and top-k, computed here
            mine = [np.asarray(a) for a in tail(eng.params, x)]
            for a, b in zip(mine, full):
                np.testing.assert_array_equal(a, b)
            assert [a.shape for a in got] == [(rows,), (rows, k), (rows, k)]
            for a, b in zip(got, mine):
                np.testing.assert_array_equal(a, b[head_lanes])
            np.testing.assert_array_equal(ev.topv, got[1])
            np.testing.assert_array_equal(ev.topi, got[2])
            # an entry of `emitted` starts at its row of the fetched
            # arrays and its tokens come from the rows after it, the
            # request's own lanes side by side
            # (benchmark/lib/olmoe_cell.emitted_logits reads so)
            assert len(ev.emit_lanes) == len(ev.emitted) == ev.emitters
            for (req, n), row in zip(ev.emitted, ev.emit_lanes):
                mine_rows = want[row:row + n]
                assert [rid for _, rid in mine_rows] == [req.rid] * n
                assert [ln for ln, _ in mine_rows] == list(
                    range(lanes[row], lanes[row] + n))
            hit += bool(about(ev, lanes))
    assert hit, f"no step of the kind {case} is about"
    assert len(steps) > 3


@pytest.mark.parametrize("max_seqs,budget,spec,rows", [
    (4, 16, 0, 4),      # a row a sequence
    (4, 64, 6, 28),     # and its drafts (tests/test_speculative.py's)
    (4, 4, 6, 8),       # never more than the step's lanes
    (8, 48, 4, 40),
])
def test_head_rows_follow_the_engine_s_shapes(max_seqs, budget, spec, rows):
    eng = ServeEngine(_opt(max_seqs=max_seqs, budget=budget, spec=bool(spec)),
                      spec_tokens=spec)
    assert eng.mixed_width == budget + max_seqs
    assert eng.head_rows == rows == min(
        eng.mixed_width, max_seqs * (1 + spec))
    assert eng._program_fingerprint()["head_rows"] == rows
    out = eng.generate(_prompts((5, 9, 3)), 4)
    assert out == eng.generate_reference(_prompts((5, 9, 3)), 4)
    assert eng.compile_counts()["mixed"] == 1


def test_pack_refuses_a_plan_whose_emitters_pass_the_head_s_rows(
        monkeypatch):
    eng = ServeEngine(_opt())
    eng.warmup()
    monkeypatch.setattr(ServeEngine, "head_rows", property(lambda self: 1))
    with eng.start_session() as s:
        for p in _prompts((3, 4)):
            s.submit(p, 2)
        with pytest.raises(AssertionError, match="need 2 rows of the "
                           "step's head, which has 1"):
            s.step()
