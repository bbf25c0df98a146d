"""What the benchmark gained with the cell `lfm2moe-longanswer` (PR 58):
one configuration and one cell, appended, and the cell's name appended
to the `.olmoe` entries whose readers read what it emits — nothing else
of BENCHMARK.json moved; the configuration holds the catalog's numbers,
its traffic is the same for every seed, the metric files of its entries
read nothing from an untraced run, the reference takes the published
layout and imports nothing of the program, the check's planted faults
are the program's and not the reference's, and the cell rehearses on the
CPU from start to verdict."""

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

from lib import conv_counts, lfm2moe_cell, traffic_gen  # noqa: E402

CELL, CONFIG = "lfm2moe-longanswer", "lfm2-24b-a2b-1chip-l10"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PARENT = "c26084bc1e31768eca80dd5c9b05101bc3465e57"


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
    cell at the end of `tpot_p50_ms.olmoe`'s list and of all 25 `.olmoe`
    per-layer entries' and of no other; `per_layer` stays at 128 and
    benchmark/metrics at its 134 files. Where git has the parent, what
    is left is the parent's file, key for key."""
    rest = _without_the_cell(BENCHMARK)
    assert (len(rest["configs"]), len(rest["workloads"]),
            len(rest["per_layer"])) == (9, 10, 128)
    assert len(os.listdir(os.path.join(BENCH, "metrics"))) == 134
    assert sorted(m["name"] for m in METRICS) == sorted(
        m["name"] for g in ("end_to_end", "per_layer") for m in BENCHMARK[g]
        if m["name"].endswith(".olmoe"))
    assert len(METRICS) == 26
    assert (BENCHMARK["run_seconds"], BENCHMARK["command"]) == (
        rest["run_seconds"], rest["command"])
    got = subprocess.run(["git", "show", f"{PARENT}:BENCHMARK.json"],
                         cwd=ROOT, capture_output=True, text=True)
    if got.returncode == 0:
        assert json.loads(got.stdout) == rest


def test_the_cell_reports_the_olmoe_names():
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"], cell["traffic"]) == (
        CONFIG, 1, CELL)
    assert len(cell["why"]) <= 200 and "10 of 40 layers" in cell["why"] \
        and "attention" in cell["why"]
    e2e = [m["name"] for m in BENCHMARK["end_to_end"]
           if CELL in m.get("workloads", ())]
    assert e2e == ["tpot_p50_ms.olmoe"]
    for m in METRICS[1:]:
        assert m["moves"] == "tpot_p50_ms.olmoe", m["name"]
    for name in ("step_ms.olmoe", "moe_share.olmoe", "experts_share.olmoe",
                 "expert_hbm_share.olmoe", "expert_load_max_over_mean.olmoe",
                 "attn_hbm_share.olmoe", "attn_kernel_share.olmoe",
                 "kv_write_share.olmoe", "dense_share.olmoe",
                 "lane_occupancy.olmoe", "unscoped_share.olmoe",
                 "chip_probe_tflops.olmoe"):
        assert name in [m["name"] for m in METRICS], name
    # every name, every `why` and every source is inside the contract
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == CONFIG)
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200
    assert len(json.dumps(BENCHMARK, indent=1)) < 64 * 1024


def test_the_configuration_holds_the_catalog_s_numbers_but_the_reduced():
    conf = _json(BENCH, "configs", CONFIG + ".json")
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "layer_types", "max_position_embeddings"]
    assert conf["source"] == entry["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    published = {
        "model_type": "lfm2_moe", "vocab_size": 65536, "hidden_size": 2048,
        "intermediate_size": 11776, "moe_intermediate_size": 1536,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "num_experts": 64, "num_experts_per_tok": 4, "num_dense_layers": 2,
        "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
        "norm_topk_prob": True, "use_expert_bias": True,
        "routed_scaling_factor": 1,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
    assert {k: conf[k] for k in published} == published
    if os.path.exists(CATALOG):         # the row itself, where it is
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-24B-A2B")
        assert row["source_url"] == conf["source"]
        assert {k: conf[k] for k in row["config"]
                if k not in conf["reduced"]} == {
            k: v for k, v in row["config"].items()
            if k not in conf["reduced"]}
        assert {k: row["config"][k] for k in conf["reduced"]} \
            == conf["published"]
        assert conf["layer_types"] == row["config"]["layer_types"][:10]
    assert conf["num_hidden_layers"] == 10 == len(conf["layer_types"])
    assert conf["max_position_embeddings"] == 4096
    # two dense layers (both convolutions), then two whole periods
    assert conf["layer_types"] == ["conv", "conv"] + [
        "full_attention", "conv", "conv", "conv"] * 2
    assert conf["published"]["num_hidden_layers"] == 40
    assert conf["published"]["max_position_embeddings"] == 128000
    args = lfm2moe_cell.model_args(conf)
    assert args == {"heads": 32, "kv_heads": 8, "experts_per_token": 4,
                    "theta": 1e6, "eps": 1e-5}
    assert len(conf["assumed"]) >= 8
    for key in ("deployment", "memory", "system_why", "rehearsal"):
        assert conf[key]
    assert "first of four" in conf["deployment"].lower()
    assert "tied" in " ".join(conf["assumed"]).lower()
    assert set(conf["check"]) == {"requests", "logit_margin", "logit_rms"}
    assert {"logit_margin", "logit_rms", "what_is_compared"} <= set(
        conf["check_why"])
    init = conf["init"]
    assert set(init) == {"norm", "final_norm", "qk_norm", "taps",
                         "expert_bias_std", "stds"}
    assert set(init["stds"]) == {
        "table", "conv_in", "conv_out", "wq", "wk", "wv", "wo", "gate_up",
        "down", "router", "expert_in", "expert_out"}
    # the three taps of comparable size; a query's scores over random
    # keys after the per-head norms: 5-8 (the mean square of the two
    # norms' weights)
    lo, hi, signed = init["taps"]
    assert signed == "signed" and hi / lo < 3
    lo, hi = init["qk_norm"]
    assert 5.0 <= (lo * lo + lo * hi + hi * hi) / 3 <= 8.0
    assert 0 < init["expert_bias_std"] < 0.1
    assert sum(a.startswith("init") for a in conf["assumed"]) >= 3
    s = conf["system"]
    assert s["serve_prefill_budget"] == 512 and s["kv_page_size"] == 16
    assert 192 <= s["serve_max_seqs"] <= 384
    assert not s["serve_spec_decode"] and not s["serve_prefix_cache"]
    assert {s[k] for k in ("compute_dtype", "param_dtype", "kv_dtype")} \
        == {"bfloat16"}
    r = conf["rehearsal"]
    assert (r["hidden_size"], r["num_attention_heads"],
            r["num_key_value_heads"], r["num_experts"],
            r["num_experts_per_tok"], r["moe_intermediate_size"],
            r["intermediate_size"], r["num_hidden_layers"],
            r["vocab_size"], r["system"]["serve_max_seqs"]) == (
        64, 4, 2, 8, 2, 32, 96, 6, 512, 8)
    # the bytes the issue reckons, from shapes
    assert conv_counts.tail_bytes_per_seq(8, 2048) == 65536
    assert 2 * 8 * 64 * 2 * 2 == 4096
    assert (s["kv_num_pages"] - 1) * 16 * 4096 == 2 << 30


def test_two_seeds_offer_the_same_prompts_at_the_same_times():
    t = _json(BENCH, "traffic", CELL + ".json")
    a = traffic_gen.make_requests(t, 1, 65536, 300)
    b = traffic_gen.make_requests(t, 5800000000, 65536, 300)
    assert [(len(r.prompt), r.max_new, r.due_s, r.tenant) for r in a] == \
        [(len(r.prompt), r.max_new, r.due_s, r.tenant) for r in b]
    assert a[0].prompt != b[0].prompt           # the seed makes the ids
    lens = np.asarray([len(r.prompt) for r in a])
    assert lens.min() >= 64 + 32 and lens.max() <= 64 + 2048
    outs = np.asarray([r.max_new for r in a])
    assert outs.min() >= 128 and outs.max() <= 1536
    assert 1 <= min(min(r.prompt) for r in b)
    assert max(max(r.prompt) for r in b) > 60000    # the whole vocabulary
    # the table of ISSUE 58, Tentpole 5, letter for letter
    assert t["arrival"] == "poisson" and "burst_factor" not in t
    assert (t["tenants"], t["tenant_zipf"], t["prefix_tokens"]) == (
        8, 1.1, 64)
    assert t["tail"] == {"dist": "pareto", "pareto_a": 2.0, "mean": 256,
                         "min": 32, "max": 2048}
    assert t["output"] == {"dist": "pareto", "pareto_a": 2.0, "mean": 512,
                           "min": 128, "max": 1536}
    assert (t["ramp_s"], t["drain_s"], t["trace_s"]) == (30, 150, 5)
    assert t["driver"] == "open_loop_lfm2moe"
    assert t["rate_rps"] / t["knee_rps"] in (
        pytest.approx(0.8), pytest.approx(0.75), pytest.approx(0.85))
    assert t["knee_why"] and t["lengths_source"]
    # the pool outlasts ramp + window at the knee, and a prompt and its
    # answer fit the served positions
    assert a[-1].due_s > 0 and t["pool_requests"] \
        >= (t["ramp_s"] + 51) * t["knee_rps"]
    conf = _json(BENCH, "configs", CONFIG + ".json")
    assert 64 + 2048 + 1536 <= conf["max_position_embeddings"]


def test_the_driver_names_the_program_s_new_modules_at_its_top():
    with open(os.path.join(BENCH, "drivers", "open_loop_lfm2moe.py")) as f:
        head = f.read().split("def run")[0]
    assert "import flexflow_tpu.models.lfm2_moe" in head
    assert "from flexflow_tpu.serve.arch import LFM2MoE" in head


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "lib", "reference_lfm2moe.py")) as f:
        text = f.read()
    imports = [ln for ln in text.splitlines()
               if ln.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import jax",
                       "import jax.numpy as jnp"]
    assert 'default_matmul_precision("highest")' in text
    # the convolution as shifted products, the experts by a plain loop
    assert "padded[j:j + s] * w[j]" in text and "lax.scan(add" in text


def test_the_published_layout_is_slices_and_reshapes_of_the_program_s():
    e, hq, hk, d, f = 3, 4, 2, 2, 5
    ones = np.ones((e,), np.float32)
    wq = np.arange(e * hq * d, dtype=np.float32).reshape(e, hq, d)
    wo = np.arange(hq * d * e, dtype=np.float32).reshape(hq, d, e)
    gu = np.arange(e * 2 * f, dtype=np.float32).reshape(e, 2 * f)
    named = lambda *names: {k: np.full((2,), i, np.float32)
                            for i, k in enumerate(names)}
    params = {
        "tok_embed": {"kernel": ones}, "embedding_norm": {"scale": 3 * ones},
        "layer0_operator_norm": {"scale": ones},
        "layer0_ffn_norm": {"scale": 2 * ones},
        "layer0_conv": named("w_in", "conv_w", "w_out"),
        "layer0_mlp": {"w_gu": gu, "w_down": ones},
        "layer1_operator_norm": {"scale": ones},
        "layer1_ffn_norm": {"scale": 2 * ones},
        "layer1_attn": {"wq": wq, "wk": wq[:, :hk], "wv": wq[:, hk:],
                        "wo": wo, "q_norm": ones[:2], "k_norm": 2 * ones[:2]},
        "layer1_moe": named("gate", "expert_bias", "wg", "wu", "wd")}
    pub = lfm2moe_cell.published_params(params, {
        "layer_types": ["conv", "full_attention"], "num_dense_layers": 1})
    conv, attn = pub["layers"]
    assert (conv["in_proj"][0], conv["conv"][0], conv["out_proj"][0]) == (
        0, 1, 2)
    np.testing.assert_array_equal(conv["w1"], gu[:, :f])
    np.testing.assert_array_equal(conv["w3"], gu[:, f:])
    assert "router" not in conv and "q_proj" not in conv
    np.testing.assert_array_equal(attn["q_proj"], wq.reshape(e, hq * d))
    np.testing.assert_array_equal(attn["k_proj"],
                                  wq[:, :hk].reshape(e, hk * d))
    np.testing.assert_array_equal(attn["o_proj"], wo.reshape(hq * d, e))
    assert attn["k_layernorm"][0] == 2 and attn["ffn_norm"][0] == 2
    assert (attn["router"][0], attn["expert_bias"][0], attn["w1"][0],
            attn["w3"][0], attn["w2"][0]) == (0, 1, 2, 3, 4)
    assert pub["embedding_norm"][0] == 3 and "lm_head" not in pub


FAULTS = ("tail_zeroed", "tail_holds_h", "gates_swapped", "silu_after_taps",
          "bias_in_weights", "no_bias", "softmax_scores", "no_renorm",
          "qk_norm_whole", "dense_as_experts")


@pytest.mark.parametrize("name", FAULTS)
def test_a_planted_fault_is_in_the_program_and_is_taken_out_again(name):
    import check_lfm2moe_logits as chk
    from flexflow_tpu.models.lfm2_moe import CONV
    from flexflow_tpu.ops import short_conv as SC
    from flexflow_tpu.serve import arch as A
    from flexflow_tpu.serve import mixers
    parts = lambda: (SC.segmented, SC.project, SC.gate_out, A.route_top_k,
                     A.LFM2MoE.__dict__["qkv"], A.LFM2MoE.__dict__["ffn"],
                     mixers.BODIES[CONV])
    sound = parts()
    with chk.faulty_program(name):
        assert parts() != sound
    assert parts() == sound
    assert chk.VARIANTS == ("base", "fp8_pages", "wrong_page",
                            "tail_swap") + FAULTS


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse-cpu", "--seed", "5800000007", "--seconds", "3"],
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
    assert numbers["conv_lanes"] == 5 * numbers["live_lanes"] > 0
    assert numbers["ssm_runs"] > 0 and numbers["expert_dropped"] == 0
    # four routing layers of six: two slots a live lane a layer
    assert numbers["expert_slots"] == 2 * 4 * numbers["live_lanes"]
    assert numbers["cache_bytes_per_token"] == 2 * 16 * 2 * 2
    assert numbers["cache_bytes_per_seq"] == 5 * 2 * 64 * 2
    assert 0.0 <= numbers["whole_chunk_step_share"] <= 1.0
    engine = json.loads(next(
        ln for ln in lines if ln.startswith("# engine: "))[10:])
    assert engine["arch"] == "lfm2_moe" and engine["layers"] == 6
    assert engine["kinds"] == "ccfccc" and engine["dense_layers"] == 2
    assert engine["conv_tail_shape"] == [5, 9, 128]
