"""What the two hot programs say about themselves (ISSUE 24,
docs/observability.md "Device scopes" / "Phase spans"):

  * scopes — the lowered text of the mixed serve step and of the train
    step holds the named scopes a profiler trace is read by;
  * spans — `Telemetry.timed` is the one phase-span entry point: with
    the bus on it records every phase of `ServeSession.step` and
    `FFModel.train_batch`, with the bus off it appends to no ring and
    takes no lock, and tokens are identical either way with zero
    recompiles;
  * counters — `Request.t_admit` is stamped at the admission with
    telemetry off, before the admitting step dispatches;
    `kv_read_bytes` and `work_items` equal a brute-force walk of the
    kernel's index maps; `StepEvents` and the `dispatch` span carry
    them;
  * stores — a program store written before the scopes existed is
    refused.
"""

import pickle
import time

import jax
import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.kernels.paged_ragged_v2 import (Q_ROWS, kv_read_bytes,
                                                  work_items)
from flexflow_tpu.serve import ServeEngine
from flexflow_tpu.utils import telemetry as T

VOCAB = 89


@pytest.fixture(scope="module")
def lm():
    from flexflow_tpu.models.transformer import build_transformer_lm
    cfg = FFConfig(batch_size=1, kv_page_size=8, kv_num_pages=73,
                   serve_max_seqs=8, serve_prefill_budget=48)
    return build_transformer_lm(cfg, vocab_size=VOCAB, max_seq_len=64,
                                hidden=32, num_heads=4, num_layers=2,
                                ff_dim=64)


def _prompts(n, lo=4, hi=40, seed=0):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(1, VOCAB, size=rng.randint(lo, hi)))
            for _ in range(n)]


# ------------------------------------------------------------- scopes
def _mixed_lowered(eng) -> str:
    c = eng.cache_cfg
    z = np.zeros((eng.mixed_width,), np.int32)
    pts = np.zeros((c.max_seqs, c.pages_per_seq), np.int32)
    return jax.jit(eng._mixed_impl).lower(
        eng._step_params, eng._device_pool(), z, z, z, z, pts, z, z + 1,
        z[:eng.head_rows], z - 1, z[:eng.head_rows]
    ).as_text(debug_info=True)


def test_mixed_step_lowers_with_its_scopes(lm):
    text = _mixed_lowered(ServeEngine(lm))
    for path in ("serve_step/embed/", "serve_step/layer0/ln/",
                 "serve_step/layer0/qkv/", "serve_step/layer1/kv_write/",
                 "serve_step/layer1/attn/", "serve_step/layer0/attn_out/",
                 "serve_step/layer1/ffn/", "serve_step/head/",
                 "serve_step/sample/"):
        assert path in text, path
    # the pools' scatter is what `kv_write` holds, the gather through
    # the page tables (the jnp twin of the kernel) what `attn` holds
    assert "serve_step/layer0/kv_write/scatter" in text
    assert "serve_step/layer0/attn/jit(_take)" in text


def test_quantized_mixed_step_keeps_the_scopes():
    from flexflow_tpu.models.transformer import build_transformer_lm
    cfg = FFConfig(batch_size=1, kv_page_size=8, kv_num_pages=41,
                   serve_max_seqs=4, serve_prefill_budget=16,
                   kv_dtype="int8")
    m = build_transformer_lm(cfg, vocab_size=VOCAB, max_seq_len=32,
                             hidden=32, num_heads=4, num_layers=1,
                             ff_dim=64)
    text = _mixed_lowered(ServeEngine(m))
    # quantize-and-scatter, the scale pools' scatters included
    assert text.count("serve_step/layer0/kv_write/scatter") >= 4
    assert "serve_step/layer0/kv_write/reduce_max" in text   # the amax


@pytest.fixture(scope="module")
def trainer():
    from flexflow_tpu import AdamOptimizer
    from flexflow_tpu.models.transformer import build_transformer_lm
    cfg = FFConfig(batch_size=2, seed=3)
    m = build_transformer_lm(cfg, vocab_size=VOCAB, max_seq_len=16,
                             hidden=32, num_heads=4, num_layers=2,
                             ff_dim=64)
    m.compile(optimizer=AdamOptimizer(lr=1e-3),
              loss_type="sparse_categorical_crossentropy", metrics=[])
    return m


def _train_batch(m, seed=0):
    rng = np.random.RandomState(seed)
    toks = rng.randint(0, VOCAB, size=(2, 16)).astype(np.int32)
    name = m.input_tensors[0].name
    batch = {name: toks, "label": np.roll(toks, -1, axis=1)}
    if len(m.input_tensors) > 1:
        batch[m.input_tensors[1].name] = np.broadcast_to(
            np.arange(16, dtype=np.int32), (2, 16)).copy()
    return batch


def test_train_step_lowers_with_its_scopes(trainer):
    ex = trainer.executor
    batch = ex.shard_batch(_train_batch(trainer))
    text = ex.build_train_step().lower(
        trainer.state, batch, jax.random.PRNGKey(0), ex._lr()
    ).as_text(debug_info=True)
    weighted = [op.name for op in trainer.ops if op.weight_specs()]
    assert weighted
    for name in weighted:
        # forward and backward of every op that holds weights
        assert f"jvp({name})/" in text, name
        assert f"transpose(jvp({name}))/" in text, name
    assert "jvp(loss)/" in text and "transpose(jvp(loss))/" in text
    assert "/optimizer/" in text


def test_train_step_scopes_survive_remat():
    from flexflow_tpu import SGDOptimizer
    from flexflow_tpu.models.transformer import build_transformer_lm
    cfg = FFConfig(batch_size=2, remat=True)
    m = build_transformer_lm(cfg, vocab_size=VOCAB, max_seq_len=16,
                             hidden=32, num_heads=4, num_layers=1,
                             ff_dim=64)
    m.compile(optimizer=SGDOptimizer(lr=0.01),
              loss_type="sparse_categorical_crossentropy", metrics=[])
    ex = m.executor
    text = ex.build_train_step().lower(
        m.state, ex.shard_batch(_train_batch(m)), jax.random.PRNGKey(0),
        ex._lr()).as_text(debug_info=True)
    name = next(op.name for op in m.ops if op.weight_specs())
    assert f"({name})" in text and "/optimizer/" in text


# -------------------------------------------------------------- spans
SERVE_PHASES = {"serve_step", "sweep", "schedule", "pack", "drain",
                "upload", "dispatch", "fetch", "emit"}


def test_serve_phase_spans_on_off_identical_zero_recompiles(lm):
    prompts = _prompts(6)
    off = ServeEngine(lm)
    assert off.telemetry is T.telemetry_for()      # the shared bus
    off.warmup()
    out_off = off.generate(prompts, 5)
    assert len(off.telemetry.events) == 0
    tel = T.Telemetry()
    on = ServeEngine(lm, telemetry=tel)
    counts = on.warmup()
    assert on.generate(prompts, 5) == out_off
    assert on.compile_counts() == counts
    spans = [e for e in tel.events if e[0] == "X"
             and e[1] == on._ENGINE_TRACK]
    assert SERVE_PHASES <= {e[2] for e in spans}
    disp = [e for e in spans if e[2] == "dispatch"]
    steps = [e for e in spans if e[2] == "step"]
    assert len(disp) == len(steps) > 0
    for i, e in enumerate(disp):
        a = e[6]
        assert a["step"] == i and a["kv_bytes"] > 0
        # the kernel's live work items and the query rows they hold
        assert a["rows"] >= a["items"] >= 1
        # speculation's draft lanes are live beside the two kinds
        assert a["live"] >= a["prefill"] + a["decode"] > 0
    # every phase lies inside its step's `serve_step` span
    whole = [(e[3], e[3] + e[4]) for e in spans if e[2] == "serve_step"]
    for e in spans:
        if e[2] in SERVE_PHASES - {"serve_step"}:
            assert any(a - 1e-9 <= e[3] and e[3] + e[4] <= b + 1e-9
                       for a, b in whole), e[2]


class _NoLock:
    """Stands in for the bus's lock: taking it is the failure."""

    def __enter__(self):
        raise AssertionError("a disabled bus took its lock")

    def __exit__(self, *exc):
        return False


def test_disabled_bus_appends_nothing_and_takes_no_lock(lm, trainer,
                                                        monkeypatch):
    bus = T.telemetry_for()
    assert not bus.enabled
    monkeypatch.setattr(bus, "_lock", _NoLock())
    with bus.timed(("p", "t"), "x", {"n": 1}):
        pass
    eng = ServeEngine(lm)
    eng.warmup()
    with eng.start_session() as s:
        s.submit(_prompts(1)[0], 3)
        while s.has_work():
            s.step()
    loss = float(trainer.train_batch(_train_batch(trainer))["loss"])
    assert np.isfinite(loss)
    assert len(bus.events) == 0 and bus.dropped_events == 0


def test_timed_records_span_and_args_when_enabled():
    tel = T.Telemetry()
    t0 = time.perf_counter()
    with tel.timed(("p", "t"), "phase", {"k": 3}):
        time.sleep(0.002)
    (ph, track, name, ts, dur, ident, args), = list(tel.events)
    assert (ph, track, name, args) == ("X", ("p", "t"), "phase", {"k": 3})
    assert dur >= 0.002 and 0 <= ts <= time.perf_counter() - t0 + 1
    assert not hasattr(tel, "async_span")
    assert T.PHASE_PREFIX == "ff:"


def test_train_batch_phase_spans_on_off_identical(trainer):
    import copy
    batch = _train_batch(trainer, seed=5)
    state0 = jax.tree_util.tree_map(np.asarray, trainer.state)
    step0 = trainer._host_step
    assert trainer.telemetry is None
    loss_off = float(trainer.train_batch(copy.deepcopy(batch))["loss"])
    counts = dict(trainer.compile_counts())
    # rewind, then the same step with the bus on
    trainer.state = jax.tree_util.tree_map(
        lambda a, ref: jax.device_put(a, ref.sharding), state0,
        trainer.state)
    trainer._host_step = step0
    tel = T.Telemetry()
    trainer.telemetry = tel
    try:
        loss_on = float(trainer.train_batch(batch)["loss"])
    finally:
        trainer.telemetry = None
    assert loss_on == loss_off
    assert dict(trainer.compile_counts()) == counts
    names = [e[2] for e in tel.events]
    assert names == ["shard_batch", "rng", "dispatch", "train_step"]


# ----------------------------------------------------------- counters
def test_t_admit_is_stamped_at_admission_with_telemetry_off(lm):
    eng = ServeEngine(lm)
    assert not eng.telemetry.enabled
    eng.warmup()
    seen = []
    real = eng._dispatch_mixed

    def stamped(*a, **k):
        seen.append(time.perf_counter())
        return real(*a, **k)

    eng._dispatch_mixed = stamped
    with eng.start_session() as s:
        reqs = [s.submit(p, 3) for p in _prompts(10, seed=2)]
        assert all(r.t_admit == 0.0 for r in reqs)
        first_step_of = {}
        while s.has_work():
            n = len(seen)
            ev = s.step()
            for r in ev.plan.admitted:
                first_step_of.setdefault(r.rid, n)
            assert ev.kv_bytes_read > 0 if ev.dispatched else True
            if ev.dispatched:
                assert ev.attn_rows >= ev.attn_items >= 1
                if ev.plan.num_prefill_lanes == 0:
                    # a decode-only step: one row an item
                    assert ev.attn_rows == ev.attn_items
    assert len(first_step_of) == len(reqs) and len(seen) > 1
    assert len(set(first_step_of.values())) > 1     # not all at once
    for r in reqs:
        t_dispatch = seen[first_step_of[r.rid]]
        # after the submit, before the admitting step's dispatch
        assert r.t_submit <= r.t_admit < t_dispatch, r.rid
        if first_step_of[r.rid]:
            # and after the step before it: the wait ends at ITS step
            assert r.t_admit > seen[first_step_of[r.rid] - 1]


def test_queue_wait_span_ends_at_the_admission(lm):
    tel = T.Telemetry()
    eng = ServeEngine(lm, telemetry=tel)
    eng.warmup()
    with eng.start_session() as s:
        reqs = [s.submit(p, 2) for p in _prompts(10, seed=4)]
        while s.has_work():
            s.step()
    ends = {e[5]: e[3] for e in tel.events
            if e[0] == "e" and e[2] == "queue_wait"}
    begins = {e[5] for e in tel.events
              if e[0] == "b" and e[2] == "queue_wait"}
    assert set(ends) == begins == {r.rid for r in reqs}
    steps = sorted(e[3] for e in tel.events
                   if e[0] == "X" and e[2] == "step")
    for r in reqs:
        assert abs(ends[r.rid] - (r.t_admit - tel._t0)) < 1e-9
        # before its first chunk's step span begins, not a step later
        first = min(e[3] for e in tel.events
                    if e[0] == "X" and e[2] in ("prefill", "decode")
                    and e[6]["rid"] == r.rid)
        assert ends[r.rid] <= first
        assert first in steps


def _walk_index_maps(lane_lens, lane_slots, page_tables, ps, bp, qb,
                     bytes_k, bytes_s):
    """The kernel's grid, one work item at a time, as the work list
    lays it out (kernels/paged_ragged_v2.py "work list"): tile by tile,
    run by run (consecutive lanes of a tile that name one slot), one
    item per kv-block below the run's longest lane. Page slot i of an
    item is one pipelined operand: its block is the page at table
    column blk * bp + i while that page holds a position the run sees,
    else the page the slot held before — and it is fetched when that
    differs from what the operand held at the previous grid step (at
    the first step, always). -> (bytes, items, rows)."""
    pad = -len(lane_lens) % qb
    lens = list(lane_lens) + [1] * pad
    slots = list(lane_slots) + [0] * pad
    total = items = rows = 0
    held = None
    for tile in range(len(lens) // qb):
        lo = 0
        while lo < qb:
            hi = lo + 1
            while hi < qb and slots[tile * qb + hi] == slots[tile * qb + lo]:
                hi += 1
            longest = max(lens[tile * qb + lo:tile * qb + hi])
            for blk in range(-(-longest // (bp * ps))):
                now = []
                for i in range(bp):
                    col = blk * bp + i
                    if col * ps < longest:
                        now.append(int(page_tables[slots[tile * qb + lo],
                                                   col]))
                    else:
                        now.append(held[i] if held else 0)
                for i in range(bp):
                    if held is None or now[i] != held[i]:
                        total += 2 * bytes_k + 2 * bytes_s     # K and V
                held = now
                items += 1
                # the lanes past the arrays' end pad the last tile
                rows += max(0, min(tile * qb + hi, len(lane_lens))
                            - (tile * qb + lo))
            lo = hi
    return total, items, rows


@pytest.mark.parametrize("itemsize,quantized", [(4, False), (2, False),
                                                (1, True)])
@pytest.mark.parametrize("bp", [1, 2, 3])
def test_kv_read_bytes_equals_a_walk_of_the_index_maps(itemsize,
                                                       quantized, bp):
    ps, h, d, pp, seqs = 4, 2, 8, 7, 5
    rng = np.random.RandomState(10 * bp + itemsize)
    # distinct physical pages per sequence, page 0 the sink
    pt = np.zeros((seqs, pp), np.int32)
    pt[1:] = 1 + rng.permutation((seqs - 1) * pp).reshape(seqs - 1, pp)
    # a ragged step: a multi-lane chunk of sequence 1 (positions 5..11),
    # single decode lanes of 2..4 (one of them a full table), a
    # one-token lane, then inactive lanes on the sink
    lens = list(range(6, 13)) + [17, pp * ps, 1] + [1, 1, 1]
    slots = [1] * 7 + [2, 3, 4] + [0, 0, 0]
    page = 2 * ps * h * d * itemsize + (2 * ps * h * 4 if quantized
                                        else 0)
    for qb in (Q_ROWS, 4):      # one tile; tiles that cut the chunk
        got = kv_read_bytes(np.array(lens), np.array(slots), pt,
                            page_size=ps, num_heads=h, head_dim=d,
                            kv_itemsize=itemsize, block_kv_pages=bp,
                            quantized=quantized, q_rows=qb)
        want, items, rows = _walk_index_maps(
            lens, slots, pt, ps, bp, qb, ps * h * d * itemsize,
            ps * h * 4 if quantized else 0)
        assert got == want > 0
        work = work_items(np.array(lens), np.array(slots), pt,
                          page_size=ps, block_kv_pages=bp, q_rows=qb)
        assert (work["total"], work["rows"]) == (items, rows)
        assert work["page_fetches"] * page == got
    # the chunk's seven lanes SHARE their fetches: in one tile they
    # read the sequence's three live pages once, not once a lane, and
    # the tile's padding lanes read the sink page (in blocks of one
    # page nothing is fetched twice, nothing dead at all)
    one = kv_read_bytes(np.array(lens[:7]), np.array(slots[:7]), pt,
                        page_size=ps, num_heads=h, head_dim=d,
                        kv_itemsize=itemsize, block_kv_pages=1,
                        quantized=quantized)
    assert one == (-(-max(lens[:7]) // ps) + 1) * page == 4 * page
    # tiles of four cut the chunk in two runs: the second fetches again
    two = kv_read_bytes(np.array(lens[:7]), np.array(slots[:7]), pt,
                        page_size=ps, num_heads=h, head_dim=d,
                        kv_itemsize=itemsize, block_kv_pages=1,
                        quantized=quantized, q_rows=4)
    assert two == (3 + 3 + 1) * page    # 9, then 12 tokens; a pad lane


def test_kv_read_bytes_of_an_idle_step_is_one_sink_page():
    pt = np.zeros((3, 5), np.int32)
    got = kv_read_bytes(np.ones(16, np.int32), np.zeros(16, np.int32),
                        pt, page_size=4, num_heads=2, head_dim=8,
                        kv_itemsize=2, block_kv_pages=2)
    assert got == 2 * (2 * 4 * 2 * 8 * 2)          # two slots, K and V


# ------------------------------------------------------------- stores
def test_a_store_from_before_the_scopes_is_refused(tmp_path):
    from flexflow_tpu.core import programs
    reg = programs.ProgramRegistry({"kind": "test"},
                                   cache_dir=str(tmp_path))
    f = jax.jit(lambda x: x * 2.0)
    reg.call("f", f, np.ones(4, np.float32))
    assert reg.save() == 1
    path = reg._store_path()
    cold = programs.ProgramRegistry.load(str(tmp_path), {"kind": "test"})
    assert cold.restored_counts() == {"f": 1}
    with open(path, "rb") as fh:
        doc = pickle.loads(fh.read())
    assert doc["version"] == programs._STORE_VERSION >= 3
    doc["version"] = 2              # as written before the scopes
    with open(path, "wb") as fh:
        fh.write(pickle.dumps(doc))
    with pytest.warns(UserWarning, match="unreadable store"):
        old = programs.ProgramRegistry.load(str(tmp_path),
                                            {"kind": "test"})
    assert sum(old.restored_counts().values()) == 0


def test_compile_cache_key_covers_the_scopes(tmp_path, monkeypatch):
    from flexflow_tpu.utils import cache_dirs
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", False)
        cache_dirs.arm_compile_cache()
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", before)
