"""What the chip bring-up (PR 21) established, checked where a CPU can.

  * no hidden fallback — chip_smoke.py and bench.py exit non-zero on a
    CPU backend and print no result; an unknown TPU device_kind is an
    error in bench.py and the machine model; a calibration that throws
    propagates; a selected flash kernel that raises propagates out of
    ops/attention.py; the paged-attention choice is one resolved,
    reported decision.
  * one compile-cache resolver — JAX_COMPILATION_CACHE_DIR if set (and
    then no code touches the config), <checkout>/.scratch/xla_cache
    otherwise, never a temporary name; --program-cache-dir places the
    *.ffprog snapshots only.
  * one process per chip — _ensure_n_devices starts no child; pool
    replicas own disjoint device sets and the placement function
    refuses over-subscription on a tpu platform.
  * built from what git would commit — the native library is keyed by
    a hash of csrc/, not by mtimes.
  * the smoke's own logic, at a tiny width with kernels interpreted.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name):
    spec = importlib.util.spec_from_file_location(
        f"{name}_under_test", os.path.join(ROOT, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod     # dataclasses resolve the module
    spec.loader.exec_module(mod)
    return mod


def _run(script, *args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script, *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


# ------------------------------------------------- exit codes off the chip
def test_chip_smoke_refuses_a_cpu_backend():
    r = _run("chip_smoke.py")
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout      # no result, no phases
    assert "no TPU" in r.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = _run("chip_smoke.py", cwd=str(tmp_path))
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout


def test_bench_refuses_a_cpu_backend_without_cpu_only():
    r = _run("bench.py")
    assert r.returncode != 0
    assert r.stdout.strip() == "", r.stdout
    assert "--cpu-only" in r.stderr


def test_unknown_tpu_device_kind_is_an_error(monkeypatch):
    from flexflow_tpu.parallel.mesh import MachineSpec
    from flexflow_tpu.search import machine_model

    bench = _load("bench")
    assert bench.peak_for("TPU v5 lite") == 197e12
    assert bench.peak_for("TPU v5 lite", bench.PEAK_HBM_BW) == 819e9
    with pytest.raises(SystemExit, match="no peak"):
        bench.peak_for("TPU v9 imaginary")
    with pytest.raises(SystemExit, match="no peak"):
        bench.peak_for("cpu")                    # the "cpu" rows are gone

    class Dev:
        platform = "tpu"
        device_kind = "TPU v9 imaginary"

    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(ValueError, match="no MachineSpec"):
        machine_model.default_machine_model()
    Dev.device_kind = "TPU v5 lite"
    mm = machine_model.default_machine_model()
    assert mm.spec.peak_flops == MachineSpec.v5e().peak_flops


def test_calibration_that_throws_propagates(monkeypatch):
    from flexflow_tpu.search import measure
    from flexflow_tpu.search.machine_model import default_machine_model

    def boom(*a, **k):
        raise RuntimeError("device lost")

    monkeypatch.setattr(measure, "measure_matmul_efficiency", boom)
    with pytest.raises(RuntimeError, match="device lost"):
        measure.calibrate(default_machine_model())


# ------------------------------------------------------ kernel decisions
def test_selected_flash_kernel_that_raises_propagates():
    """use_flash=True on a backend the kernel cannot compile for: the
    op must raise, not quietly run the XLA path."""
    from flexflow_tpu import FFConfig, FFModel
    from flexflow_tpu.op import OpContext

    ff = FFModel(FFConfig())
    x = ff.create_tensor((2, 128, 64), name="x")
    ff.multihead_attention(x, x, x, 64, 4, causal=True, use_flash=True,
                           name="mha")
    op = ff.ops[0]
    params = {n: jnp.zeros(s.shape, jnp.float32)
              for n, s in op.weight_specs().items()}
    xin = jnp.ones((2, 128, 64), jnp.float32)
    with pytest.raises(NotImplementedError, match="tpu backend"):
        op.forward(params, [xin] * 3, OpContext(training=False))
    assert op.attn_impl == "flash"
    # auto on the same backend resolves to the XLA path up front
    ff2 = FFModel(FFConfig())
    x2 = ff2.create_tensor((2, 128, 64), name="x")
    ff2.multihead_attention(x2, x2, x2, 64, 4, causal=True, name="mha")
    ff2.ops[0].forward(params, [xin] * 3, OpContext(training=False))
    assert ff2.ops[0].attn_impl == "xla"


def test_paged_impl_is_one_resolved_reported_decision(monkeypatch):
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.kernels import paged_ragged_v2 as k
    from flexflow_tpu.models.transformer import build_transformer_lm
    from flexflow_tpu.serve import ServeEngine

    assert k.resolve_paged_impl(None, False) == k.JNP        # cpu: auto
    assert k.resolve_paged_impl(False, True) == k.JNP        # asked
    assert k.resolve_paged_impl(None, True) == k.PALLAS_INTERPRET
    with pytest.raises(RuntimeError, match="tpu backend"):
        k.resolve_paged_impl(True, False)    # not a quiet jnp run
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert k.resolve_paged_impl(None, False) == k.PALLAS     # tpu: auto
    assert k.resolve_paged_impl(True, False) == k.PALLAS
    assert k.resolve_paged_impl(False, False) == k.JNP       # asked
    monkeypatch.undo()

    cfg = FFConfig(batch_size=1, kv_page_size=8, kv_num_pages=33,
                   serve_max_seqs=2, serve_prefill_budget=16)
    lm = build_transformer_lm(cfg, vocab_size=53, max_seq_len=48,
                              hidden=32, num_heads=4, num_layers=1,
                              ff_dim=64)
    eng = ServeEngine(lm, interpret=True)
    assert eng.attn_impl == k.PALLAS_INTERPRET
    assert eng._program_fingerprint()["attn_impl"] == k.PALLAS_INTERPRET
    out = eng.generate([[3, 5, 7, 11, 2]], 3)
    st = eng.last_stats
    assert st["attn_impl"] == k.PALLAS_INTERPRET
    assert st["nonfinite_logit_steps"] == 0 and st["devices"] == [0]
    assert out == ServeEngine(lm, use_pallas=False).generate(
        [[3, 5, 7, 11, 2]], 3)


# ------------------------------------------------- the cache-dir resolver
@pytest.fixture
def _restore_cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_cache_dir_resolver(monkeypatch, tmp_path, _restore_cache_config):
    from flexflow_tpu.core.programs import ProgramRegistry
    from flexflow_tpu.utils import cache_dirs

    # unset: the checkout's ignored .scratch/, a fixed path
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("FLEXFLOW_TPU_CACHE", raising=False)
    want = os.path.join(ROOT, ".scratch", "xla_cache")
    assert cache_dirs.compile_cache_dir() == want
    assert tempfile.gettempdir() not in want
    assert str(os.getpid()) not in want
    path, _ = cache_dirs.arm_compile_cache()
    assert path == want == jax.config.jax_compilation_cache_dir
    assert cache_dirs.arm_compile_cache()[0] == want     # and stable
    # the per-machine measurement caches live with it
    assert cache_dirs.measurement_cache_dir() == os.path.join(
        want, "flexflow_tpu")
    from flexflow_tpu.search import cost_cache, measure
    assert measure.cache_file("calibration", "TPU v5 lite").startswith(want)
    assert cost_cache.default_path().startswith(want)

    # set: JAX read the variable itself — no code path may then call
    # jax.config.update("jax_compilation_cache_dir", ...)
    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    calls = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (calls.append(k), real_update(k, v)))
    path, was_empty = cache_dirs.arm_compile_cache()
    assert path == env_dir and was_empty
    # --program-cache-dir places the *.ffprog snapshots and nothing else
    prog_dir = tmp_path / "progs"
    reg = ProgramRegistry({"kind": "test"}, cache_dir=str(prog_dir))
    reg.call("fam", jax.jit(lambda x: x + 1), jnp.zeros((3,)))
    assert reg.save() == 1
    assert "jax_compilation_cache_dir" not in calls
    assert sorted(p.suffix for p in prog_dir.iterdir()) == [
        ".ffprog", ".json"]
    assert not (prog_dir / "xla").exists()
    assert cache_dirs.measurement_cache_dir() == os.path.join(
        env_dir, "flexflow_tpu")


# ---------------------------------------------------- one process per chip
def test_ensure_n_devices_starts_no_child(monkeypatch):
    graft = _load("__graft_entry__")

    def no_child(*a, **k):
        raise AssertionError("a child process would take the chip")

    monkeypatch.setattr(subprocess, "run", no_child)
    monkeypatch.setattr(subprocess, "Popen", no_child)
    graft._ensure_n_devices(8)       # the 8 virtual devices are there
    with pytest.raises(RuntimeError, match="needs 64 devices.*has 8"):
        graft._ensure_n_devices(64)


def test_replica_placement():
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import build_transformer_lm
    from flexflow_tpu.parallel.mesh import replica_devices
    from flexflow_tpu.serve import ReplicaPool

    devs = jax.devices()
    assert replica_devices(0, 1) == (devs[0],)
    assert replica_devices(3, 2) == (devs[6], devs[7])
    # the virtual-CPU platform wraps (tests build more replicas than
    # devices) — a tpu platform refuses, naming both numbers
    assert replica_devices(4, 2) == (devs[0], devs[1])
    with pytest.raises(ValueError, match=r"chips \[8, 10\).*has 8"):
        replica_devices(4, 2, platform="tpu")
    assert replica_devices(3, 2, platform="tpu") == (devs[6], devs[7])

    cfg = FFConfig(batch_size=1, kv_page_size=4, kv_num_pages=33,
                   serve_max_seqs=2, serve_prefill_budget=8,
                   serve_spec_decode=False)
    lm = build_transformer_lm(cfg, vocab_size=61, max_seq_len=48,
                              hidden=32, num_heads=4, num_layers=1,
                              ff_dim=64)
    with ReplicaPool(lm, num_replicas=4) as pool:
        owned = [r.engine.devices for r in pool.replicas]
        assert owned == [(devs[i],) for i in range(4)]
        for r in pool.replicas:
            eng = r.engine
            on = {d for leaf in jax.tree_util.tree_leaves(
                (eng._step_params, eng._device_pool()))
                for d in leaf.devices()}
            assert on == set(eng.devices)     # weights AND pages
        fps = {r.engine.programs.fp_hash for r in pool.replicas}
        assert len(fps) == 4     # one program store per device set
    with ReplicaPool(lm, num_replicas=2,
                     engine_kwargs={"tensor_parallel": 2}) as pool:
        assert [r.engine.devices for r in pool.replicas] == [
            (devs[0], devs[1]), (devs[2], devs[3])]


# ------------------------------------------------- the native library key
def test_native_library_is_keyed_by_source_bytes(monkeypatch, tmp_path):
    from flexflow_tpu import native

    if not native.available():
        pytest.skip("no toolchain: nothing to key")
    real_lib = native.lib_path()
    assert native.source_hash() in os.path.basename(real_lib)
    csrc = tmp_path / "csrc"
    shutil.copytree(native._CSRC, csrc)
    monkeypatch.setattr(native, "_CSRC", str(csrc))
    monkeypatch.setattr(native, "_BUILD_DIR", str(tmp_path / "_build"))
    builds = []

    def fake_build(verbose=False):     # g++ is slow; the decision is
        os.makedirs(native._BUILD_DIR, exist_ok=True)   # what is tested
        shutil.copy(real_lib, native.lib_path())
        builds.append(native.lib_path())
        return native.lib_path()

    monkeypatch.setattr(native, "build", fake_build)

    def fresh():
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_load_failed", False)
        assert native.get_lib() is not None
        return native.status()

    assert "built now" in fresh() and len(builds) == 1
    assert "found built" in fresh() and len(builds) == 1
    # one byte changes, the mtime does not: the old check could never
    # notice; the hash does
    src = csrc / "simulator.cc"
    stat = os.stat(src)
    with open(src, "ab") as f:
        f.write(b" ")
    os.utime(src, ns=(stat.st_atime_ns, stat.st_mtime_ns))
    assert os.stat(src).st_mtime_ns == stat.st_mtime_ns
    assert "built now" in fresh() and len(builds) == 2
    assert builds[0] != builds[1]


# ------------------------------------------------------ the smoke's logic
def test_smoke_logic_at_a_tiny_width(capsys, monkeypatch, tmp_path,
                                     _restore_cache_config):
    """chip_smoke.run end to end on one virtual device: same phases,
    same checks, kernels through the Pallas interpreter."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    smoke = _load("chip_smoke")
    tiny = smoke.Widths(hidden=64, heads=4, ffn=128, vocab=97,
                        positions=640, layers=1, train_layers=1,
                        train_batch=1, new_tokens=3)
    rc = smoke.run(tiny, interpret=True, require_tpu=False, max_devices=1)
    lines = capsys.readouterr().out.strip().splitlines()
    # the last line is the driver's contract: these keys and no other
    assert rc == 0 and json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}, lines
    assert lines[-2].startswith("details: ")
    result = json.loads(lines[-2][len("details: "):])
    assert list(result["phases"]) == ["kernel", "train", "serve"]
    assert all(p["ok"] for p in result["phases"].values())
    serve = result["phases"]["serve"]
    assert serve["attn_impl"] == "pallas_interpret"
    assert serve["lanes"] == 520 and serve["prefix_hit_tokens"] > 0
    assert serve["compile_counts"]["mixed"] == 1
    train = result["phases"]["train"]
    assert train["losses"][-1] < train["losses"][0]
    assert [ln.split(":")[0] for ln in lines if ln.startswith("phase ")] \
        == ["phase kernel", "phase train", "phase serve"]
    assert result["cache_dir"] == str(tmp_path / "xla")
    # a failing phase is an exit code and "ok": false, and ends the run
    monkeypatch.setattr(smoke, "kernel_phase",
                        lambda *a: (_ for _ in ()).throw(
                            AssertionError("kernel disagrees")))
    rc = smoke.run(tiny, interpret=True, require_tpu=False, max_devices=1)
    out = capsys.readouterr().out.strip().splitlines()
    bad = json.loads(out[-1])
    assert rc == 1 and bad["ok"] is False
    assert sorted(bad) == ["device", "ok"]
    assert list(json.loads(out[-2][len("details: "):])["phases"]) \
        == ["kernel"]
    assert "phase kernel: FAIL" in out[-5]
