"""The benchmark's trace readers, run as tier-1 tests (they need no
accelerator): `benchmark/check_program_trace.py` — the wire decoder
against `jax.profiler.ProfileData` on the recorded traces (names,
starts, durations; `tf_op`, `flops`, `bytes_accessed` found on
small_trace.xplane.pb), scope-path parsing, every reduction on
hand-made operations and on the trace recorded on the chip, the
readers' None on a program without scopes — and
`benchmark/check_trace_reduce.py`, the reduction they build on."""

import importlib.util
import os

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def _load(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("check", [
    "check_paths", "check_synthetic", "check_small", "check_readers",
    "check_scoped"])
def test_program_trace(check):
    getattr(_load("check_program_trace"), check)()


@pytest.mark.parametrize("check", [
    "check_intervals", "check_synthetic", "check_recorded"])
def test_trace_reduce(check):
    getattr(_load("check_trace_reduce"), check)()


@pytest.mark.parametrize("check", [
    "check_span_ratio", "check_kernel_time_per_count",
    "check_host_gap_phase", "check_metric_files"])
def test_live_counters(check):
    """`benchmark/check_live_counters.py`: the readers of the step's
    live counts and of the host's gaps by phase, on hand-made
    operations and spans, and their metric files on runs that hold
    nothing for them."""
    getattr(_load("check_live_counters"), check)()


def test_every_metric_of_the_benchmark_has_its_files():
    """Each per-layer entry names a metric file, and that a reader."""
    import json
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(
            BENCH, "readers", spec["reader"] + ".py")), m["name"]
        assert set(m.get("workloads", [])) <= cells
        assert m.get("moves", "setup_s") in e2e, m["name"]
