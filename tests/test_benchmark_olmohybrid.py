"""What the benchmark gained with the cell `olmohybrid-ragchat` (PR 52):
its entries and files name things that exist, the configuration holds
the catalog's numbers, its traffic is the same for every seed, the
metric files of its entries read nothing from an untraced run, the
reference takes the published projection layout and imports nothing of
the program, the check's planted faults are the program's and not the
reference's, and the cell rehearses on the CPU from start to verdict."""

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

from test_benchmark_sala import (EIGHTEEN,  # noqa: E402
                                 assert_cell_and_its_entries)

from lib import delta_counts, olmohybrid_cell, traffic_gen  # noqa: E402

CELL, CONFIG = "olmohybrid-ragchat", "olmo-hybrid-7b-1chip-l16"
TWO_MORE = ["attn_hbm_share.olmoe", "attn_ns_per_live_step.olmoe"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


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


def test_the_cell_keeps_its_place_and_its_entries_still_list_it():
    # the 18 entries `sala-longdoc` is on, and two of `olmoe-chat`'s
    # whose readers take the paged kernel's spans and name as they are
    layer = assert_cell_and_its_entries(CELL, CONFIG, EIGHTEEN + TWO_MORE)
    by_name = {m["name"]: m for m in layer}
    assert all("sala-longdoc" in by_name[n]["workloads"] for n in EIGHTEEN)
    assert all("sala-longdoc" not in by_name[n]["workloads"]
               and "olmoe-chat" in by_name[n]["workloads"]
               for n in TWO_MORE)


def test_the_configuration_holds_the_catalog_s_numbers_but_the_reduced():
    conf = _json(BENCH, "configs", CONFIG + ".json")
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "max_position_embeddings"]
    assert conf["source"] == entry["source"]
    published = {
        "model_type": "olmo_hybrid", "vocab_size": 100352,
        "hidden_size": 3840, "intermediate_size": 11008,
        "num_attention_heads": 30, "num_key_value_heads": 30,
        "hidden_act": "silu", "attention_bias": False,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None},
        "layer_types": (["linear_attention"] * 3
                        + ["full_attention"]) * 8}
    assert {k: conf[k] for k in published} == published
    if os.path.exists(CATALOG):         # the row itself, where it is
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Olmo-Hybrid-7B")
        assert row["source_url"] == conf["source"]
        assert {k: v for k, v in row["config"].items()
                if k not in conf["reduced"]} == published
        assert {k: row["config"][k] for k in conf["reduced"]} \
            == conf["published"]
    assert {k: conf[k] for k in conf["reduced"]} == {
        "num_hidden_layers": 16, "max_position_embeddings": 32768}
    assert conf["published"] == {
        "num_hidden_layers": 32, "max_position_embeddings": 65536}
    # four whole periods: delta, delta, delta, full, four times
    args = olmohybrid_cell.model_args(conf)
    assert args["layer_types"] == tuple(published["layer_types"][:16])
    assert (args["heads"], args["linear_heads"], args["theta"],
            args["beta_scale"]) == (30, 30, None, 2.0)
    assert len(conf["assumed"]) >= 8
    for key in ("deployment", "memory", "system_why", "rehearsal"):
        assert conf[key]
    assert "TWO pipeline stages" in conf["deployment"]
    assert set(conf["check"]) == {"requests", "logit_margin", "logit_rms"}
    assert {"logit_margin", "logit_rms", "what_is_compared"} <= set(
        conf["check_why"])
    # the assumed initialisation: the branches' size is the post-norm's
    # scale, a peaked softmax, steps small enough for the state to
    # remember
    assert set(conf["init"]) == {"post_norm", "final_norm", "qk_norm",
                                 "delta_norm", "dt", "matrix_std"}
    assert 0.2 <= conf["init"]["post_norm"][0] \
        <= conf["init"]["post_norm"][1] <= 0.5
    assert conf["init"]["dt"][1] <= 0.1 < 1.0
    assert conf["init"]["qk_norm"][0] >= 2.0
    assert sum(a.startswith("init") for a in conf["assumed"]) >= 3
    s = conf["system"]
    assert (s["serve_prefill_budget"], s["serve_max_seqs"]) == (512, 32)
    # the bytes the issue reckons, from shapes
    assert delta_counts.state_bytes_per_seq(12, 30, 30, 96, 192) \
        == 12 * (2211840 + 69120)
    assert 4 * 30 * 128 * 2 * 2 == 61440


def test_two_seeds_offer_the_same_prompts_at_the_same_times():
    t = _json(BENCH, "traffic", CELL + ".json")
    a = traffic_gen.make_requests(t, 1, 100352, 60)
    b = traffic_gen.make_requests(t, 5200000000, 100352, 60)
    assert [(len(r.prompt), r.max_new, r.due_s, r.tenant) for r in a] == \
        [(len(r.prompt), r.max_new, r.due_s, r.tenant) for r in b]
    assert a[0].prompt != b[0].prompt           # the seed makes the ids
    lens = np.asarray([len(r.prompt) for r in a])
    assert lens.min() >= 64 + 256 and lens.max() <= 64 + 12288
    outs = np.asarray([r.max_new for r in a])
    assert outs.min() >= 64 and outs.max() <= 1024
    assert max(max(r.prompt) for r in b) > 37984    # the whole vocabulary
    assert (t["tenants"], t["prefix_tokens"], t["ramp_s"]) == (8, 64, 20)
    assert t["driver"] == "open_loop_olmohybrid"
    assert t["rate_rps"] / t["knee_rps"] in (pytest.approx(0.8),
                                             pytest.approx(0.6))
    assert t["knee_why"] and t["lengths_source"]
    # a prompt and its answer fit the served positions
    conf = _json(BENCH, "configs", CONFIG + ".json")
    assert 64 + 12288 + 1024 <= conf["max_position_embeddings"]
    assert 64 + 12288 + 1024 <= max(olmohybrid_cell.SEQ_BUCKETS)


def test_the_driver_names_the_program_s_new_modules_at_its_top():
    with open(os.path.join(BENCH, "drivers",
                           "open_loop_olmohybrid.py")) as f:
        head = f.read().split("def run")[0]
    assert "import flexflow_tpu.models.olmo_hybrid" in head
    assert "from flexflow_tpu.serve.arch import OlmoHybrid" in head


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "lib", "reference_olmohybrid.py")) as f:
        text = f.read()
    imports = [ln for ln in text.splitlines()
               if ln.startswith(("import ", "from "))]
    assert imports == ["from __future__ import annotations", "import math",
                       "import jax", "import jax.numpy as jnp"]
    assert 'default_matmul_precision("highest")' in text
    assert "beta_scale * jax.nn.sigmoid(b)" in text and "lax.scan" in text


def test_the_published_layout_is_a_permutation_of_the_fused_one():
    """`published_params` on a marked fused matrix: head h's q, k, v
    and z columns land where the published one-matrix-a-projection
    layout has them."""
    hv, dk, dv, e = 3, 2, 4, 5
    conf = {"linear_num_value_heads": hv, "linear_key_head_dim": dk,
            "linear_value_head_dim": dv, "num_hidden_layers": 1,
            "layer_types": ["linear_attention"]}
    width = 2 * dk + 2 * dv
    cols = np.arange(hv * width)
    fused = np.broadcast_to(cols, (e, hv * width)).astype(np.float32)
    ones = np.ones((e,), np.float32)
    params = {
        "tok_embed": {"kernel": ones}, "final_norm": {"scale": ones},
        "lm_head": {"kernel": ones},
        "layer0_post_norm1": {"scale": ones},
        "layer0_post_norm2": {"scale": ones},
        "layer0_mlp": {"w_gu": np.arange(12.0).reshape(2, 6),
                       "w_down": ones},
        "layer0_delta": {
            "w_qkvz": fused, "w_ba": np.broadcast_to(
                np.arange(2.0 * hv), (e, 2 * hv)),
            "conv_w": ones, "A_log": ones, "dt_bias": ones,
            "o_norm": ones, "wo": ones}}
    layer = olmohybrid_cell.published_params(params, conf)["layers"][0]
    of = lambda lo, n: np.concatenate(
        [h * width + lo + np.arange(n) for h in range(hv)])
    np.testing.assert_array_equal(layer["q_proj"][0], of(0, dk))
    np.testing.assert_array_equal(layer["k_proj"][0], of(dk, dk))
    np.testing.assert_array_equal(layer["v_proj"][0], of(2 * dk, dv))
    np.testing.assert_array_equal(layer["g_proj"][0], of(2 * dk + dv, dv))
    np.testing.assert_array_equal(layer["b_proj"][0], [0, 2, 4])
    np.testing.assert_array_equal(layer["a_proj"][0], [1, 3, 5])
    np.testing.assert_array_equal(layer["gate_proj"], [[0, 1, 2], [6, 7, 8]])
    np.testing.assert_array_equal(layer["up_proj"], [[3, 4, 5], [9, 10, 11]])


@pytest.mark.parametrize("name", ["beta_not_doubled", "no_correction",
                                  "pre_norm", "qk_norm_per_head"])
def test_a_planted_fault_is_in_the_program_and_is_taken_out_again(name):
    import check_olmohybrid_logits as chk
    from flexflow_tpu.kernels import gated_delta_scan as KD
    from flexflow_tpu.ops import gated_delta as GD
    from flexflow_tpu.serve.arch import OlmoHybrid
    parts = lambda: (GD.gates, GD._token, GD._chunk, KD.supported,
                     OlmoHybrid.__dict__["norm1"],
                     OlmoHybrid.__dict__["ffn"],
                     OlmoHybrid.__dict__["branch_norm"],
                     OlmoHybrid.__dict__["qkv"])
    sound = parts()
    with chk.faulty_program(name):
        assert parts() != sound
    assert parts() == sound
    assert name in chk.VARIANTS and chk.VARIANTS[0] == "base"


def test_the_counts_of_a_block_and_a_lane_from_shapes():
    # a lane touches every element of the state seven times; a block of
    # 64 lanes in the chunk form is some 0.4 GFLOP a layer at the
    # published heads (PERF.md section 5 holds its seconds)
    assert delta_counts.lane_flops(30, 96, 192) == 7 * 552960
    assert 0.3e9 < delta_counts.chunk_block_flops(30, 96, 192) < 0.5e9
    assert delta_counts.channels(30, 30, 96, 192) == 11520


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse-cpu", "--seed", "5200000007", "--seconds", "3"],
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
    assert numbers["delta_lanes"] > 0
    assert 0.0 <= numbers["whole_chunk_step_share"] <= 1.0
    engine = json.loads(next(
        ln for ln in lines if ln.startswith("# engine: "))[10:])
    # the rehearsal's six heads of 24 x 64 are a shape the kernel takes
    # (interpreted here), their slab in pairs as the published one is
    assert engine["kinds"] == "dddf"
    assert engine["delta_impl"] == "pallas_interpret"
    assert engine["delta_state_layout"] == "head_pairs"
