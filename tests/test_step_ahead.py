"""One step in flight ahead of the host (ServeSession._step / _land):
step N+1 is planned and dispatched while step N runs, the one token of
N that N+1 needs is read on the device, and N is fetched and emitted
after that dispatch. The tokens are those of the same session with every
step landed first and of the no-cache reference, for every served
description; a request that leaves while it holds a lane in flight has
its row dropped; what needs a token's VALUE for the next plan (a draw, a
draft) or changes who runs (a last token) lands first; and a call hands
out the events of ONE step, the one that landed in it.
"""

import time

import numpy as np
import pytest

from test_cmdaplus import VOCAB as CMDA_VOCAB, _lm as _cmda_lm
from test_head_rows import _echo, _opt
from test_minicpm_sala import VOCAB as SALA_VOCAB, _lm as _sala_lm
from test_olmoe import VOCAB as OLMOE_VOCAB, _lm as _olmoe_lm
from test_phi4flash import VOCAB as PHI_VOCAB, _lm as _phi_lm

from flexflow_tpu.config import CompMode, FFConfig
from flexflow_tpu.models.transformer import build_transformer_lm
from flexflow_tpu.serve import ServeEngine
from flexflow_tpu.serve import engine as E
from flexflow_tpu.serve.scheduler import RequestOutcome, SampleParams
from flexflow_tpu.utils.faults import FaultInjector

OPT_VOCAB = 89


def _opt_cached(**cfg):
    """tests/test_head_rows.py's tiny OPT with the prefix cache on and
    pages of 4 tokens: a decode lane completes a page every fourth
    step, often on a token still in flight."""
    base = dict(batch_size=1, seed=3, kv_page_size=4, kv_num_pages=97,
                serve_max_seqs=4, serve_prefill_budget=16,
                serve_spec_decode=False)
    base.update(cfg)
    lm = build_transformer_lm(FFConfig(**base), vocab_size=OPT_VOCAB,
                              max_seq_len=96, hidden=32, num_heads=4,
                              num_layers=2, ff_dim=64)
    lm.compile(comp_mode=CompMode.INFERENCE)
    return lm


def _stream(vocab, seed=3, shared=32):
    """Prompts with a shared prefix (a hit where the cache matches
    prefixes), one longer than any budget here (chunked), more than the
    slots, and answers of staggered lengths (one of a single token)."""
    rng = np.random.default_rng(seed)

    def toks(n):
        return rng.integers(1, vocab, n).tolist()

    head = toks(shared)
    prompts = [head + toks(9), toks(75), head + toks(14), toks(5),
               toks(20), toks(41)]
    return prompts, [6, 3, 9, 1, 12, 5]


def _landed_first(monkeypatch):
    """Every step lands before the next is planned: the order the
    session had before a step could run ahead."""
    monkeypatch.setattr(E.ServeSession, "_lands_first",
                        lambda self, emitters, spec_emitters: True)


ENGINES = {
    "opt": (_opt_cached, OPT_VOCAB, {}),
    "olmoe": (_olmoe_lm, OLMOE_VOCAB, {"interpret": True}),
    "phi4flash": (_phi_lm, PHI_VOCAB, {"interpret": True}),
    "cmdaplus": (_cmda_lm, CMDA_VOCAB, {"interpret": True}),
    "minicpm_sala": (_sala_lm, SALA_VOCAB, {"interpret": True}),
}


# ------------------------------------------------------ (i) token parity
@pytest.mark.parametrize("which", list(ENGINES))
def test_tokens_are_the_reference_s_with_and_without_a_step_in_flight(
        which, monkeypatch):
    make, vocab, kw = ENGINES[which]
    eng = ServeEngine(make(), **kw)
    eng.warmup()
    prompts, new = _stream(vocab, shared=16 if which == "opt" else 32)
    ahead = eng.generate(prompts, new)
    st = eng.last_stats
    assert st["steps_ahead"] > 0 and st["lanes_dropped"] == 0
    assert st["steps"] == st["steps_dispatched"] > st["steps_ahead"]
    if eng.cache.prefix_enabled:
        assert st["prefix_hit_tokens"] > 0
    assert max(len(p) for p in prompts) > eng.prefill_budget
    assert ahead == eng.generate_reference(prompts, new)
    with monkeypatch.context() as m:
        _landed_first(m)
        first = eng.generate(prompts, new)
        assert eng.last_stats["steps_ahead"] == 0
    assert ahead == first
    assert eng.compile_counts()["mixed"] == 1
    eng.cache.check_invariants(eng.pool)


# ----------------------------------------- (ii) an EOS met while in flight
def test_an_eos_in_flight_drops_the_overshoot_and_commits_nothing_of_it(
        monkeypatch):
    prompts, new = _stream(OPT_VOCAB, shared=16)
    new = [20] * len(prompts)
    probe = ServeEngine(_opt_cached())
    free = probe.generate_reference(prompts, new)
    # a token some request emits mid-answer at a position that ENDS a
    # page of 4: the overshoot lane of the step after rewrites that
    # position and so completes the page, which a landing that did not
    # drop the chunk would commit
    eos = next(out[j] for p, out in zip(prompts, free) for j in range(3, 12)
               if (len(p) + j + 1) % 4 == 0 and out[j] not in out[:j])
    want = probe.generate_reference(prompts, new, eos_token=eos)
    assert any(len(w) < n for w, n in zip(want, new)), "no EOS was met"

    def run():
        eng = ServeEngine(_opt_cached())
        eng.warmup()
        out = eng.generate(prompts, new, eos_token=eos)
        eng.cache.check_invariants(eng.pool)
        return out, eng.last_stats, dict(eng.cache.stats)

    got, st, cache = run()
    assert got == want
    # each request that met the EOS mid-answer held a lane in the step
    # after it: nobody read that row
    met = sum(len(w) < n for w, n in zip(want, new))
    assert st["lanes_dropped"] == met and st["steps_ahead"] > 0
    with monkeypatch.context() as m:
        _landed_first(m)
        got_first, st_first, cache_first = run()
    assert got_first == want and st_first["lanes_dropped"] == 0
    # the overshoot lanes wrote K/V past the EOS; none of those pages
    # reached the prefix cache, and the slots (4 for 6 prompts) were
    # taken again
    assert cache["pages_committed"] == cache_first["pages_committed"]
    assert st["steps_dispatched"] >= st_first["steps_dispatched"]


# ------------------ (iii) leaving the running set with a lane in flight
def _drive_until_in_flight(s, req):
    """Step until `req` is decoding and holds a row of the step in
    flight."""
    for _ in range(64):
        s.step()
        fl = s._flight
        if fl is not None and req.rid in fl.rows and len(req.out_tokens) >= 2:
            return
    raise AssertionError("the request never held a lane in flight")


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_a_request_aborted_in_flight_loses_its_row_and_nothing_else(how):
    eng = ServeEngine(_opt_cached())
    eng.warmup()
    prompts, _ = _stream(OPT_VOCAB, shared=16)
    want = eng.generate_reference(prompts, 16)
    eng.cache.clear_prefix()
    with eng.start_session() as s:
        reqs = [s.submit(p, 16) for p in prompts]
        victim = reqs[1]
        _drive_until_in_flight(s, victim)
        had = len(victim.out_tokens)
        if how == "cancel":
            assert eng.cancel(victim.rid)
        else:
            victim.t_deadline = time.perf_counter() - 1.0
        while s.has_work():
            ev = s.step()
            assert ev is None or victim not in [r for r, _ in ev.emitted]
            eng.cache.check_invariants(eng.pool)
        stats = s.stats_dict()
    assert victim.outcome == (RequestOutcome.CANCELLED if how == "cancel"
                              else RequestOutcome.DEADLINE_EXPIRED)
    # the token it had in flight never landed; what it had is a prefix
    assert victim.out_tokens == want[1][:had] and victim.inflight == 0
    assert stats["lanes_dropped"] == 1
    for r, w in zip(reqs, want):
        if r is not victim:
            assert r.out_tokens == w
    assert eng.cache.free_pages == eng.cache_cfg.usable_pages
    assert eng.cache.free_slots == eng.cache_cfg.max_seqs


def test_a_preemption_in_flight_drops_the_row_and_the_token_comes_again():
    # injected page pressure over the plans 4-8 evicts requests that
    # hold lanes of the step in flight (tests/test_request_observability)
    inj = FaultInjector("serve.page_pressure:exhaust:0.9@4-8", seed=0)
    eng = ServeEngine(_opt_cached(kv_num_pages=33, serve_prefill_budget=24),
                      faults=inj)
    eng.warmup()
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(1, OPT_VOCAB, size=rng.randint(10, 26)))
               for _ in range(8)]
    got = eng.generate(prompts, 8)
    st = eng.last_stats
    assert st["preemptions"] > 0 and st["lanes_dropped"] > 0
    assert st["steps_ahead"] > 0
    assert got == eng.generate_reference(prompts, 8)
    eng.cache.check_invariants(eng.pool)


# ------------------------------- (iv) what the next plan cannot do without
def test_a_sampled_request_lands_first_and_keeps_its_stream(monkeypatch):
    eng = ServeEngine(_opt_cached())
    eng.warmup()
    prompts, new = _stream(OPT_VOCAB, shared=16)
    temps = [0.0, 0.9, 0.0, 0.0, 0.0, 0.0]     # one request draws
    ahead = eng.generate(prompts, new, temperature=temps, top_k=8,
                         sample_seed=11)
    st = eng.last_stats
    assert 0 < st["steps_ahead"] < st["steps_dispatched"]
    with monkeypatch.context() as m:
        _landed_first(m)
        first = eng.generate(prompts, new, temperature=temps, top_k=8,
                             sample_seed=11)
    assert ahead == first
    greedy = eng.generate_reference(prompts, new)
    assert [a for a, t in zip(ahead, temps) if not t] == \
        [g for g, t in zip(greedy, temps) if not t]
    # no step is dispatched ahead of one that emits for a request that
    # draws
    eng.cache.clear_prefix()
    with eng.start_session() as s:
        for p, n, t in zip(prompts, new, temps):
            s.submit(p, n, sample=SampleParams(t, 8, 11) if t else None)
        drew = False
        while s.has_work():
            ev = s.step()
            if ev is None or not ev.dispatched:
                continue
            assert not (drew and ev.ahead)
            drew = any(r.sample is not None for r, _ in ev.emitted)
    all_draw = eng.generate(prompts, new, temperature=0.9, top_k=8,
                            sample_seed=11)
    assert eng.last_stats["steps_ahead"] == 0
    assert all_draw[1] == ahead[1]


def test_a_speculative_engine_lands_every_step_first():
    eng = ServeEngine(_echo(_opt(max_seqs=4, budget=64, spec=True)))
    eng.warmup()
    assert eng.spec_tokens > 0
    rng = np.random.RandomState(2)
    prompts = [list(rng.randint(1, OPT_VOCAB, size=n)) for n in (9, 14, 6)]
    got = eng.generate(prompts, 12)
    st = eng.last_stats
    assert st["spec_accepted_tokens"] > 0 and st["steps_ahead"] == 0
    assert got == eng.generate_reference(prompts, 12)


# ---------------------------------------- (v) what a call hands out
def test_every_call_hands_out_one_step_the_one_that_landed():
    eng = ServeEngine(_opt_cached())
    eng.warmup()
    prompts, new = _stream(OPT_VOCAB, shared=16)
    with eng.start_session() as s:
        assert s.step() is None and not s.has_work()
        reqs = [s.submit(p, n) for p, n in zip(prompts, new)]
        first = s.step()
        # the first call dispatches and lands nothing
        assert first is not None and not first.dispatched
        assert first.plan is None and not first.emitted and not first.ahead
        assert s._flight is not None and s.has_work()
        seen, tokens = [], {r.rid: 0 for r in reqs}
        while s.has_work():
            in_flight = s._flight is not None
            ev = s.step()
            if ev is None:
                break
            if not ev.dispatched:
                # nothing was in flight: the call dispatched, no more
                assert ev.plan is None and not in_flight
                continue
            seen.append(ev.step_index)
            planned = {ch.req.rid for ch in ev.plan.chunks}
            assert len(ev.emit_lanes) == len(ev.emitted) == ev.emitters
            for (req, n), row in zip(ev.emitted, ev.emit_lanes):
                # the object's own rows: the token is its top-1
                assert req.rid in planned and n == 1
                tokens[req.rid] += 1
                assert req.out_tokens[tokens[req.rid] - 1] \
                    == ev.topi[row, 0]
            for req in ev.finished:
                assert req.rid in planned and req.is_done()
            assert ev.wall_s > 0 and ev.kv_bytes_read > 0
        assert s.step() is None and s._flight is None
        stats = s.stats_dict()
    # every dispatched step landed once, in order
    assert seen == list(range(stats["steps_dispatched"]))
    assert stats["steps"] == len(seen)
    assert [r.out_tokens for r in reqs] == \
        eng.generate_reference(prompts, new)


def test_close_lands_the_step_in_flight():
    eng = ServeEngine(_opt_cached())
    eng.warmup()
    prompts, new = _stream(OPT_VOCAB, shared=16)
    s = eng.start_session()
    req = s.submit(prompts[3], 8)       # one chunk: it emits
    assert not s.step().dispatched and s._flight is not None
    s.close()
    assert s._flight is None and len(s.util) == 1
    assert len(req.out_tokens) == 1 and req.inflight == 0
    # the engine serves on (the next session reclaims the open slot)
    assert eng.generate(prompts, new) == eng.generate_reference(prompts, new)


def test_the_dispatch_span_says_whether_the_step_ran_ahead():
    from flexflow_tpu.utils.telemetry import Telemetry
    tel = Telemetry()
    eng = ServeEngine(_opt_cached(), telemetry=tel)
    eng.warmup()
    prompts, new = _stream(OPT_VOCAB, shared=16)
    eng.generate(prompts, new)
    st = eng.last_stats
    spans = [e[6] for e in tel.events if e[0] == "X" and e[2] == "dispatch"]
    assert [a["step"] for a in spans] == list(range(st["steps_dispatched"]))
    assert all(a["dispatched"] == 1 for a in spans)
    assert sum(a["ahead"] for a in spans) == st["steps_ahead"] > 0
    # a call's span holds at most one dispatch and one landing, the
    # landing step's fetch and emit after the dispatch: a step left in
    # flight, a step dispatched and one landed, a step landed first
    names = [e[2] for e in tel.events if e[0] == "X" and e[2] in (
        "dispatch", "fetch", "emit", "serve_step")]
    calls = "".join(n[0] for n in names).split("s")
    assert set(calls) == {"d", "dfe", "fe", ""}
    assert calls.count("dfe") + calls.count("d") == st["steps_dispatched"]
    assert calls.count("dfe") + calls.count("fe") == st["steps"]
