"""What the benchmark gained with the cell `falconh1-burstchat` (PR 56):
one configuration and one cell, appended, and the cell's name appended
to the `.phi` entries whose readers read what it emits — nothing else of
BENCHMARK.json moved; the configuration holds the catalog's numbers, its
traffic is the same for every seed, the metric files of its entries read
nothing from an untraced run, the reference takes the published layout
and imports nothing of the program, the check's planted faults are the
program's and not the reference's, and the cell rehearses on the CPU
from start to verdict."""

import importlib
import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

from lib import falconh1_cell, ssd_counts, traffic_gen  # noqa: E402

CELL, CONFIG = "falconh1-burstchat", "falcon-h1-34b-1chip-l6"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PARENT = "1e239a0dc5775722184716b12bcbe3a1d96bd139"


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


BENCHMARK = _json(ROOT, "BENCHMARK.json")
METRICS = [m for g in ("end_to_end", "per_layer") for m in BENCHMARK[g]
           if CELL in m.get("workloads", ())]
UNTRACED = {"numbers": {}, "trace": {}, "spans": None, "device_kind": None}


def _read(name, run):
    spec = _json(BENCH, "metrics", name + ".json")
    reader = importlib.import_module("readers." + spec["reader"])
    return reader.read(run, **spec.get("args", {}))


@pytest.mark.parametrize("name", [m["name"] for m in METRICS])
def test_every_metric_file_of_the_cell_reads_nothing_from_an_empty_run(name):
    assert _read(name, UNTRACED) is None


def _without_the_cell(bench):
    """BENCHMARK.json with what this PR (and every later one: each adds
    at the END of a list) added taken out again; raises where an
    addition is not at the end of its list."""
    out = json.loads(json.dumps(bench))
    at = [c["name"] for c in out["configs"]].index(CONFIG)
    del out["configs"][at:]
    at = [w["name"] for w in out["workloads"]].index(CELL)
    gone = {w["name"] for w in out["workloads"][at:]}
    del out["workloads"][at:]
    for group in ("end_to_end", "per_layer"):
        for m in out[group]:
            names = m.get("workloads", [])
            while names and names[-1] in gone:
                names.pop()
            assert not gone & set(names), m["name"]
    assert CELL not in json.dumps(out) and CONFIG not in json.dumps(out)
    return out


def test_the_benchmark_differs_from_its_parent_by_the_additions_alone():
    """One configuration and one cell at the end of their lists, the
    cell at the end of `tpot_p50_ms.phi`'s list and of all 25 `.phi`
    per-layer entries' and of no other; `per_layer` stays at 128 and
    benchmark/metrics at its 134 files. Where git has the parent, what
    is left is the parent's file, key for key."""
    rest = _without_the_cell(BENCHMARK)
    assert (len(rest["configs"]), len(rest["workloads"]),
            len(rest["per_layer"])) == (8, 9, 128)
    assert len(os.listdir(os.path.join(BENCH, "metrics"))) == 134
    assert sorted(m["name"] for m in METRICS) == sorted(
        m["name"] for g in ("end_to_end", "per_layer") for m in BENCHMARK[g]
        if m["name"].endswith(".phi"))
    assert len(METRICS) == 26
    got = subprocess.run(["git", "show", f"{PARENT}:BENCHMARK.json"],
                         cwd=ROOT, capture_output=True, text=True)
    if got.returncode == 0:
        assert json.loads(got.stdout) == rest


def test_the_cell_reports_the_phi_names():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"], cell["traffic"]) == (
        CONFIG, 1, CELL)
    assert len(cell["why"]) <= 200 and "34 %" in cell["why"] \
        and "6 of 72 layers" in cell["why"]
    e2e = [m["name"] for m in BENCHMARK["end_to_end"]
           if CELL in m.get("workloads", ())]
    assert e2e == ["tpot_p50_ms.phi"]
    for m in METRICS[1:]:
        assert m["moves"] in ("tpot_p50_ms.phi", "setup_s"), m["name"]
    for name in ("step_ms.phi", "ssm_share.phi", "ssm_scan_hbm_share.phi",
                 "attn_hbm_share.phi", "dense_share.phi",
                 "head_sample_share.phi", "cache_bytes_per_token.phi",
                 "lane_occupancy.phi", "unscoped_share.phi",
                 "chip_probe_tflops.phi"):
        assert name in [m["name"] for m in METRICS], name


def test_the_configuration_holds_the_catalog_s_numbers_but_the_reduced():
    conf = _json(BENCH, "configs", CONFIG + ".json")
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "max_position_embeddings"]
    assert conf["source"] == entry["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    published = {
        "model_type": "falcon_h1", "vocab_size": 261120,
        "hidden_size": 5120, "intermediate_size": 21504,
        "num_attention_heads": 20, "num_key_value_heads": 4,
        "head_dim": 128, "mamba_n_heads": 32, "mamba_d_head": 128,
        "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_n_groups": 2,
        "mamba_d_conv": 4, "mamba_chunk_size": 128, "mamba_expand": 2,
        "mamba_rms_norm": True, "mamba_norm_before_gate": False,
        "mamba_conv_bias": True, "rope_theta": 100000000000,
        "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
        "embedding_multiplier": 5.656854249492381,
        "lm_head_multiplier": 0.0078125, "ssm_in_multiplier": 0.25,
        "ssm_out_multiplier": 0.08838834764831845,
        "attention_in_multiplier": 1, "attention_out_multiplier": 0.0375,
        "key_multiplier": 0.011048543456039804,
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738]}
    assert {k: conf[k] for k in published} == published
    if os.path.exists(CATALOG):         # the row itself, where it is
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Falcon-H1-34B-Instruct")
        assert row["source_url"] == conf["source"]
        assert {k: conf[k] for k in row["config"]
                if k not in conf["reduced"]} == {
            k: v for k, v in row["config"].items()
            if k not in conf["reduced"]}
        assert {k: row["config"][k] for k in conf["reduced"]} \
            == conf["published"]
    assert {k: conf[k] for k in conf["reduced"]} == {
        "num_hidden_layers": 6, "max_position_embeddings": 8192}
    assert conf["published"] == {
        "num_hidden_layers": 72, "max_position_embeddings": 262144}
    args = falconh1_cell.model_args(conf)
    assert (args["heads"], args["kv_heads"], args["ssm_heads"],
            args["groups"], args["d_state"], args["theta"]) == (
        20, 4, 32, 2, 256, 1e11)
    assert args["mult"]["key_multiplier"] == 0.011048543456039804
    assert len(conf["assumed"]) >= 8
    for key in ("deployment", "memory", "system_why", "rehearsal"):
        assert conf[key]
    assert "twelve" in conf["deployment"].lower()
    assert set(conf["check"]) == {"requests", "logit_margin", "logit_rms"}
    assert {"logit_margin", "logit_rms", "what_is_compared"} <= set(
        conf["check_why"])
    init = conf["init"]
    assert set(init) == {"norm", "dt", "a", "stds"}
    assert set(init["stds"]) == {"table", "head", "ssm_in", "ssm_out", "wq",
                                 "wk", "wv", "wo", "gate_up", "down"}
    assert init["dt"] == [0.001, 0.1] and init["a"] == [1.0, 16.0]
    # a query's scores over random keys AFTER key_multiplier: 5-8
    dev = init["stds"]["wq"] * init["stds"]["wk"] * 5120 \
        * conf["key_multiplier"]
    assert 5.0 <= dev <= 8.0
    assert sum(a.startswith("init") for a in conf["assumed"]) >= 3
    s = conf["system"]
    assert s["serve_prefill_budget"] == 512
    assert 64 <= s["serve_max_seqs"] <= 128
    assert not s["serve_spec_decode"] and not s["serve_prefix_cache"]
    r = conf["rehearsal"]
    assert r["mamba_n_groups"] == 2 and r["mamba_n_heads"] >= 4
    assert r["num_hidden_layers"] == 4 and r["vocab_size"] == 512
    # the bytes the issue reckons, from shapes
    assert ssd_counts.state_bytes_per_seq(6, 32, 128, 2, 256) \
        == 6 * (4194304 + 30720)
    assert 6 * 4 * 128 * 2 * 2 == 12288


def test_two_seeds_offer_the_same_prompts_at_the_same_times():
    t = _json(BENCH, "traffic", CELL + ".json")
    a = traffic_gen.make_requests(t, 1, 261120, 300)
    b = traffic_gen.make_requests(t, 5600000000, 261120, 300)
    assert [(len(r.prompt), r.max_new, r.due_s, r.tenant) for r in a] == \
        [(len(r.prompt), r.max_new, r.due_s, r.tenant) for r in b]
    assert a[0].prompt != b[0].prompt           # the seed makes the ids
    lens = np.asarray([len(r.prompt) for r in a])
    assert lens.min() >= 64 + 32 and lens.max() <= 64 + 2048
    outs = np.asarray([r.max_new for r in a])
    assert outs.min() >= 32 and outs.max() <= 1024
    assert max(max(r.prompt) for r in b) > 200000   # the whole vocabulary
    # the table of ISSUE 56, Tentpole 5, letter for letter
    assert (t["arrival"], t["burst_factor"], t["burst_len"]) == (
        "bursty", 4, 32)
    assert (t["tenants"], t["tenant_zipf"], t["prefix_tokens"]) == (
        8, 1.1, 64)
    assert t["tail"] == {"dist": "pareto", "pareto_a": 2.0, "mean": 384,
                         "min": 32, "max": 2048}
    assert t["output"] == {"dist": "pareto", "pareto_a": 2.0, "mean": 256,
                           "min": 32, "max": 1024}
    assert (t["ramp_s"], t["drain_s"], t["trace_s"]) == (20, 150, 5)
    assert t["driver"] == "open_loop_falconh1"
    assert t["rate_rps"] / t["knee_rps"] in (
        pytest.approx(0.8), pytest.approx(0.75), pytest.approx(0.85))
    assert t["knee_why"] and t["lengths_source"]
    # bursts: the gaps inside a burst are a quarter of the mean's
    gaps = np.diff([r.due_s for r in a])
    assert np.percentile(gaps, 25) < 0.5 / t["rate_rps"]
    # the pool outlasts ramp + window at the knee, and a prompt and its
    # answer fit the served positions
    assert a[-1].due_s > 0 and t["pool_requests"] \
        >= (t["ramp_s"] + 51) * t["knee_rps"]
    conf = _json(BENCH, "configs", CONFIG + ".json")
    assert 64 + 2048 + 1024 <= conf["max_position_embeddings"]
    assert 64 + 2048 + 1024 <= max(falconh1_cell.SEQ_BUCKETS)


def test_the_driver_names_the_program_s_new_modules_at_its_top():
    with open(os.path.join(BENCH, "drivers", "open_loop_falconh1.py")) as f:
        head = f.read().split("def run")[0]
    assert "import flexflow_tpu.models.falcon_h1" in head
    assert "from flexflow_tpu.serve.arch import FalconH1" in head


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "lib", "reference_falconh1.py")) as f:
        text = f.read()
    imports = [ln for ln in text.splitlines()
               if ln.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import jax",
                       "import jax.numpy as jnp"]
    assert 'default_matmul_precision("highest")' in text
    assert "lax.scan(token" in text and 'mult["key_multiplier"]' in text


def test_the_published_layout_is_slices_and_reshapes_of_the_program_s():
    e, hq, hk, d, f = 3, 4, 2, 2, 5
    ones = np.ones((e,), np.float32)
    wq = np.arange(e * hq * d, dtype=np.float32).reshape(e, hq, d)
    wo = np.arange(hq * d * e, dtype=np.float32).reshape(hq, d, e)
    gu = np.arange(e * 2 * f, dtype=np.float32).reshape(e, 2 * f)
    params = {
        "tok_embed": {"kernel": ones}, "final_norm": {"scale": ones},
        "lm_head": {"kernel": ones}, "layer0_ln": {"scale": ones},
        "layer0_ln2": {"scale": 2 * ones},
        "layer0_ssm": {k: np.full((2,), i, np.float32) for i, k in enumerate(
            ("w_in", "conv_w", "conv_b", "A_log", "D", "dt_bias", "norm",
             "w_out"))},
        "layer0_attn": {"wq": wq, "wk": wq[:, :hk], "wv": wq[:, hk:],
                        "wo": wo},
        "layer0_mlp": {"w_gu": gu, "w_down": ones}}
    layer = falconh1_cell.published_params(
        params, {"num_hidden_layers": 1})["layers"][0]
    np.testing.assert_array_equal(layer["q_proj"], wq.reshape(e, hq * d))
    np.testing.assert_array_equal(layer["k_proj"],
                                  wq[:, :hk].reshape(e, hk * d))
    np.testing.assert_array_equal(layer["o_proj"], wo.reshape(hq * d, e))
    np.testing.assert_array_equal(layer["gate_proj"], gu[:, :f])
    np.testing.assert_array_equal(layer["up_proj"], gu[:, f:])
    assert layer["in_proj"][0] == 0 and layer["out_proj"][0] == 7
    assert layer["ssm_norm"][0] == 6 and layer["pre_ff_norm"][0] == 2


@pytest.mark.parametrize("name", ["ssm_out_mult_1", "key_mult_1",
                                  "group0_for_all", "whole_norm",
                                  "attn_after_ssm"])
def test_a_planted_fault_is_in_the_program_and_is_taken_out_again(name):
    import check_falconh1_logits as chk
    from flexflow_tpu.models.falcon_h1 import SSD_ATTN
    from flexflow_tpu.serve import mixers
    from flexflow_tpu.serve.arch import FalconH1
    parts = lambda: (FalconH1.__dict__["ssd_out"], FalconH1.__dict__["qkv"],
                     FalconH1.__dict__["ssd_scan_inputs"],
                     mixers.BODIES[SSD_ATTN])
    sound = parts()
    with chk.faulty_program(name):
        assert parts() != sound
    assert parts() == sound
    assert name in chk.VARIANTS and chk.VARIANTS[0] == "base"
    assert {"fp8_pages", "wrong_page", "state_swap"} <= set(chk.VARIANTS)


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse-cpu", "--seed", "5600000007", "--seconds", "3"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["correct"] is True and last["metrics"] == {}
    assert last["failed"] == 0 and last["attempted"] > 0
    assert last["rehearsal"] is True
    numbers = json.loads(next(
        ln for ln in lines if ln.startswith("# numbers: "))[11:])
    assert numbers["state_bytes"] > 0 and numbers["full_kv_bytes"] > 0
    assert numbers["paged_calls_in_place"] == numbers["paged_calls"] > 0
    assert numbers["ssd_lanes"] > 0 and numbers["ssm_runs"] > 0
    assert numbers["cache_bytes_per_token"] == 4 * 2 * 16 * 2 * 2
    assert 0.0 <= numbers["whole_chunk_step_share"] <= 1.0
    engine = json.loads(next(
        ln for ln in lines if ln.startswith("# engine: "))[10:])
    # the rehearsal's four heads of 128 x 128 in two groups are a shape
    # the kernel takes (interpreted here)
    assert engine["arch"] == "falcon_h1" and engine["layers"] == 4
    assert engine["scan_impl"] == "pallas_interpret"
    assert engine["ssd_state_shape"] == [128, 512]
