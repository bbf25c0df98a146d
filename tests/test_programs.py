"""Program registry + AOT compile cache (core/programs.py).

Layers, mirroring the module's contracts:

  * registry — exact per-family compile counting on a toy jitted
    program: one new signature per family pins the per-family
    increment (and ONLY that family's); restored executables count
    zero; corrupt stores warn and boot cold; a foreign fingerprint
    under the same dir is a silent miss.
  * fingerprint — every folded field the issue names (kv dtype,
    adapter rank, tp degree, jax version string) flips the hash AND
    misses the store; the same config reloads and hits.
  * engine — a warm reload is bit-identical (greedy tokens equal
    across the save/load boundary) on f32 AND int8 KV pools with zero
    warm compiles; export/import/adapter warmup compiles are counted
    exactly (the monitoring-snapshot coverage gap: compiles inside
    warmup_handoff / adapter load could hide from the old proxy).
"""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.config import FFConfig
from flexflow_tpu.core.programs import ProgramRegistry, fingerprint_hash
from flexflow_tpu.models.transformer import build_transformer_lm
from flexflow_tpu.serve import ServeEngine

VOCAB = 89
FAMILIES = ("mixed", "adapter", "export", "import")


def _engine(cache_dir=None, **kw):
    """The tests/test_serve.py engine idiom, with the program cache
    armed when a dir is given."""
    if cache_dir is not None:
        kw["program_cache_dir"] = str(cache_dir)
    cfg = FFConfig(batch_size=1, kv_page_size=8, kv_num_pages=73,
                   serve_max_seqs=4, serve_prefill_budget=48, **kw)
    lm = build_transformer_lm(cfg, vocab_size=VOCAB, max_seq_len=64,
                              hidden=32, num_heads=4, num_layers=2,
                              ff_dim=64)
    return ServeEngine(lm)


PROMPTS = [[3, 5, 7, 11, 2, 9, 4, 1], [6, 6, 8, 2]]


# ------------------------------------------------------------ registry
def test_per_family_increment_is_exact():
    """One new signature per program family -> that family's count
    increments by EXACTLY one and no other family moves (the registry
    replaces the max-of-two-proxies counter, so the increment must be
    exact, not >=)."""
    reg = ProgramRegistry({"kind": "test"})
    f = jax.jit(lambda x: x * 2)
    for fam in FAMILIES:
        reg.register(fam)
    for i, fam in enumerate(FAMILIES):
        x = jnp.zeros((i + 1,), jnp.float32)
        before = reg.compile_counts()
        y = reg.call(fam, f, x)                 # new signature
        assert np.array_equal(np.asarray(y), np.zeros((i + 1,)))
        after = reg.compile_counts()
        assert after[fam] == before[fam] + 1
        assert {k: v for k, v in after.items() if k != fam} \
            == {k: v for k, v in before.items() if k != fam}
        reg.call(fam, f, x)                     # same signature: cached
        assert reg.compile_counts() == after
    # a second fresh signature per family is again exactly +1
    for i, fam in enumerate(FAMILIES):
        reg.call(fam, f, jnp.zeros((i + 100,), jnp.float32))
    assert reg.compile_counts() == {fam: 2 for fam in FAMILIES}


def test_signature_keys_values_and_dtypes():
    """The signature keys on shape, dtype, static VALUES and the
    extra_key — each flip is a distinct program; repeats are not."""
    reg = ProgramRegistry({"kind": "test"})
    x = jnp.zeros((4,), jnp.float32)
    base = reg.signature((x,))
    assert reg.signature((x,)) == base
    assert reg.signature((jnp.zeros((5,), jnp.float32),)) != base
    assert reg.signature((jnp.zeros((4,), jnp.int32),)) != base
    assert reg.signature((x,), extra_key="variant") != base
    assert reg.signature((3, x)) != reg.signature((4, x))  # static value


def test_restored_executables_count_zero(tmp_path):
    """save -> load in a fresh registry: the restored executable
    dispatches bit-identically and compile_counts() stays zero (the
    warm-boot contract monitoring snapshots could never promise)."""
    fp = {"kind": "test", "v": 1}
    a = ProgramRegistry(fp, cache_dir=str(tmp_path))
    f = jax.jit(lambda x: jnp.cumsum(x) * 3)
    x = jnp.arange(6, dtype=jnp.float32)
    y = a.call("fam", f, x)
    assert a.save() == 1
    b = ProgramRegistry(fp, cache_dir=str(tmp_path))
    assert b.load_warm() == 1
    y2 = b.call("fam", f, x)
    assert np.array_equal(np.asarray(y), np.asarray(y2))
    assert sum(b.compile_counts().values()) == 0
    assert b.restored_counts()["fam"] == 1
    # a signature the store never saw still compiles (and counts)
    b.call("fam", f, jnp.arange(9, dtype=jnp.float32))
    assert b.compile_counts()["fam"] == 1


def test_restore_binds_to_the_devices_it_was_compiled_for(tmp_path):
    """The case the three tests above missed on a multi-device host: a
    program compiled for ONE device of the eight — and not device 0 —
    restores onto exactly that device (deserialize_and_load's default
    binds to all local devices and the first call dies with "expected
    8 shards"), dispatches there, and counts zero compiles."""
    dev = jax.devices()[3]
    fp = {"kind": "test", "device_ids": (dev.id,)}
    f = jax.jit(lambda x: jnp.cumsum(x) * 3)
    x = jax.device_put(jnp.arange(6, dtype=jnp.float32), dev)
    a = ProgramRegistry(fp, cache_dir=str(tmp_path))
    y = a.call("fam", f, x)
    assert y.devices() == {dev}
    assert a.save() == 1
    b = ProgramRegistry(fp, cache_dir=str(tmp_path))
    assert b.load_warm() == 1
    (restored,) = b._compiled.values()
    assert restored.runtime_executable().local_devices() == [dev]
    y2 = b.call("fam", f, x)
    assert y2.devices() == {dev}
    assert np.array_equal(np.asarray(y), np.asarray(y2))
    assert sum(b.compile_counts().values()) == 0


def test_runtime_rejection_of_a_restored_program_recompiles(tmp_path):
    """"A bad cache costs a compile and a warning, never a crash" must
    hold for the RUNTIME's rejection too (JaxRuntimeError — what a
    wrongly bound executable raises), not only jit's own argument
    checks."""
    fp = {"kind": "test", "v": 3}
    f = jax.jit(lambda x: x * 2)
    x = jnp.arange(4, dtype=jnp.float32)
    a = ProgramRegistry(fp, cache_dir=str(tmp_path))
    a.call("fam", f, x)
    a.save()
    b = ProgramRegistry(fp, cache_dir=str(tmp_path))
    assert b.load_warm() == 1
    (key,) = b._compiled

    def rejects(*args):
        raise jax.errors.JaxRuntimeError(
            "INVALID_ARGUMENT: Expected args to have 8 shards, got [1]")

    b._compiled[key] = rejects
    with pytest.warns(UserWarning, match="rejected its first call"):
        y = b.call("fam", f, x)
    assert np.array_equal(np.asarray(y), np.asarray(x) * 2)
    assert b.compile_counts()["fam"] == 1 and b.restored_counts()["fam"] == 0
    # a FRESH compile that raises is not swallowed
    c = ProgramRegistry(fp)
    c._compile = lambda name, fn, args: rejects
    with pytest.raises(jax.errors.JaxRuntimeError):
        c.call("fam", f, x)


def test_corrupt_store_warns_and_boots_cold(tmp_path):
    """cost_cache.py discipline: truncated/garbage stores cost a
    warning and a cold compile, never a crash — and save() afterwards
    replaces the bad file with a good one."""
    fp = {"kind": "test", "v": 2}
    a = ProgramRegistry(fp, cache_dir=str(tmp_path))
    f = jax.jit(lambda x: x - 1)
    a.call("fam", f, jnp.zeros((3,), jnp.float32))
    a.save()
    path = a._store_path()
    with open(path, "wb") as fh:
        fh.write(b"not a program snapshot")
    b = ProgramRegistry(fp, cache_dir=str(tmp_path))
    with pytest.warns(UserWarning, match="program cache"):
        assert b.load_warm() == 0
    b.call("fam", f, jnp.zeros((3,), jnp.float32))
    assert b.compile_counts()["fam"] == 1      # compiled cold
    assert b.save() == 1                        # store healed
    c = ProgramRegistry(fp, cache_dir=str(tmp_path))
    assert c.load_warm() == 1


def test_fingerprint_flip_misses_store(tmp_path):
    """Flipping any folded field must miss the snapshot; the same
    fingerprint must hit. (The file name IS the fingerprint hash, so a
    foreign-fingerprint dir read is a silent miss, not corruption.)"""
    fp = {"kind": "test", "jax": jax.__version__, "kv_dtype": "float32",
          "adapter_rank": 0, "tp": 1}
    a = ProgramRegistry(fp, cache_dir=str(tmp_path))
    a.call("fam", jax.jit(lambda x: x + 1), jnp.zeros((3,), jnp.float32))
    a.save()
    for field, val in [("jax", "0.0.0-not-this-jax"),
                       ("kv_dtype", "int8"),
                       ("adapter_rank", 8),
                       ("tp", 2)]:
        flipped = dict(fp)
        flipped[field] = val
        assert fingerprint_hash(flipped) != fingerprint_hash(fp), field
        b = ProgramRegistry(flipped, cache_dir=str(tmp_path))
        assert b.load_warm() == 0, field
    assert ProgramRegistry(dict(fp),
                           cache_dir=str(tmp_path)).load_warm() == 1


# --------------------------------------------------------- fingerprint
def test_engine_fingerprint_folds_serving_knobs():
    """The engine fingerprint flips on kv dtype, adapter rank and tp
    degree (the config knobs that change compiled programs without
    changing the model), and folds the jax version string."""
    base = _engine()
    h0 = fingerprint_hash(base.programs.fingerprint)
    assert base.programs.fingerprint["jax"] == jax.__version__
    assert fingerprint_hash(
        _engine(kv_dtype="int8").programs.fingerprint) != h0
    assert fingerprint_hash(
        _engine(adapter_rank=4).programs.fingerprint) != h0
    cfg = FFConfig(batch_size=1, kv_page_size=8, kv_num_pages=73,
                   serve_max_seqs=4, serve_prefill_budget=48)
    lm = build_transformer_lm(cfg, vocab_size=VOCAB, max_seq_len=64,
                              hidden=32, num_heads=4, num_layers=2,
                              ff_dim=64)
    tp = ServeEngine(lm, tensor_parallel=4)
    assert fingerprint_hash(tp.programs.fingerprint) != h0
    assert tp.programs.fingerprint["tp"] == 4
    # equal configs agree — the hit side of the contract
    assert fingerprint_hash(_engine().programs.fingerprint) == h0


# -------------------------------------------------------------- engine
@pytest.mark.parametrize("kv", ["float32", "int8"])
def test_warm_boot_is_bit_identical_and_compile_free(tmp_path, kv):
    """The tentpole gate at test scale, on BOTH pool formats: a cold
    engine populates --program-cache-dir; a second engine over the
    same config restores every program, performs ZERO compiles through
    warmup AND generation, and emits bit-identical greedy tokens."""
    d = tmp_path / kv
    cold = _engine(cache_dir=d, kv_dtype=kv)
    cold.warmup()
    assert sum(cold.compile_counts().values()) > 0   # non-vacuous
    assert cold.boot_stats is not None and not cold.boot_stats["warm"]
    out_cold = cold.generate(PROMPTS, max_new_tokens=6)
    warm = _engine(cache_dir=d, kv_dtype=kv)
    assert warm.programs_restored > 0
    warm.warmup()
    assert warm.boot_stats["warm"] is True
    assert warm.boot_stats["compile_s"] == 0.0
    assert sum(warm.compile_counts().values()) == 0
    out_warm = warm.generate(PROMPTS, max_new_tokens=6)
    assert out_warm == out_cold
    assert sum(warm.compile_counts().values()) == 0


def test_engine_corrupt_store_falls_back(tmp_path):
    """A corrupted snapshot on a live engine boots cold with the
    'program cache' warning and serves identical tokens."""
    cold = _engine(cache_dir=tmp_path)
    cold.warmup()
    out = cold.generate(PROMPTS, max_new_tokens=4)
    stores = glob.glob(str(tmp_path / "*.ffprog"))
    assert len(stores) == 1
    with open(stores[0], "wb") as fh:
        fh.write(b"garbage")
    with pytest.warns(UserWarning, match="program cache"):
        bad = _engine(cache_dir=tmp_path)
    assert bad.programs_restored == 0
    bad.warmup()
    assert sum(bad.compile_counts().values()) > 0
    assert bad.generate(PROMPTS, max_new_tokens=4) == out


def test_handoff_and_adapter_compiles_counted_exactly():
    """The coverage gap the registry closes: export/import (handoff)
    and adapter-load compiles used to happen outside the snapshotted
    window on a jax without the monitoring module. Now each costs
    exactly one counted compile, and re-running costs zero."""
    eng = _engine()
    eng.warmup()
    c0 = eng.compile_counts()
    assert c0["export"] == 0 and c0["import"] == 0
    eng.warmup_handoff()
    c1 = eng.compile_counts()
    assert c1["export"] == c0["export"] + 1
    assert c1["import"] == c0["import"] + 1
    eng.warmup_handoff()                     # cached: exact, no drift
    assert eng.compile_counts() == c1

    from flexflow_tpu.serve.adapters import make_tenant_adapters
    ae = _engine(adapter_rank=4)
    counts = ae.warmup()
    assert counts["adapter"] == 1            # warmed inside warmup()
    adapters = make_tenant_adapters(num_layers=2, hidden=32,
                                    num_heads=4, head_dim=8, ff_dim=64,
                                    rank=4, tenants=1, seed=3)
    w, sc = adapters[1]
    ae.register_adapter(1, w, scale=sc)
    assert ae.adapters.acquire(1) is not None
    ae._drain_adapter_loads()                # real load reuses warmup's
    assert ae.compile_counts()["adapter"] == 1
