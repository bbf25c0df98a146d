"""What the benchmark gained with the cell `qwen3next-longchat` (PR 49):
its entries and files name things that exist, the configuration holds
the catalog's numbers, its traffic is the same for every seed, the
metric files of its entries read nothing from an untraced run, the
check's planted faults are the program's and not the reference's, and
the cell rehearses on the CPU from start to verdict."""

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

from lib import qwen3next_cell, traffic_gen  # noqa: E402

CELL, CONFIG = "qwen3next-longchat", "qwen3-next-80b-a3b-1chip-ep4-l8"
SEVEN = ["moe_share.cmda", "experts_share.cmda", "expert_hbm_share.cmda",
         "shared_experts_share.cmda", "shared_hbm_share.cmda",
         "expert_load_max_over_mean.cmda", "expert_held_share.cmda"]


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
    # the 18 entries `sala-longdoc` is on, and the seven of
    # `cmdaplus-mixedlen` whose readers take this expert layer's scopes
    # and counters as they are
    layer = assert_cell_and_its_entries(CELL, CONFIG, EIGHTEEN + SEVEN)
    by_name = {m["name"]: m for m in layer}
    assert all("sala-longdoc" in by_name[n]["workloads"] for n in EIGHTEEN)
    assert all(by_name[n]["workloads"][0] == "cmdaplus-mixedlen"
               for n in SEVEN)


def test_the_configuration_holds_the_catalog_s_numbers_but_the_reduced():
    conf = _json(BENCH, "configs", CONFIG + ".json")
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "max_position_embeddings"]
    assert conf["source"] == entry["source"]
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "mlp_only_layers": [], "model_type": "qwen3_next",
        "moe_intermediate_size": 512, "norm_topk_prob": True,
        "num_attention_heads": 16, "num_experts_per_tok": 10,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
        "rms_norm_eps": 1e-6, "rope_scaling": None,
        "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False}
    assert {k: conf[k] for k in published} == published
    # the reduced keys, beside the published counts they were cut from
    assert {k: conf[k] for k in conf["reduced"]} == {
        "num_hidden_layers": 8, "num_experts": 128, "vocab_size": 37984,
        "max_position_embeddings": 32768}
    assert conf["published"] == {
        "num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936,
        "max_position_embeddings": 262144}
    assert conf["router_width"] == 512 and conf["experts_first"] == 0
    assert qwen3next_cell.held(conf) == (0, 128)
    assert conf["vocab_size"] * 4 == conf["published"]["vocab_size"]
    # two whole periods: delta, delta, delta, full, twice
    args = qwen3next_cell.model_args(conf)
    assert (args["num_layers"], args["interval"]) == (8, 4)
    assert (args["rotary_dim"], args["key_heads"], args["ratio"]) == (
        64, 16, 2)
    assert len(conf["assumed"]) >= 8
    for key in ("deployment", "memory", "system_why", "rehearsal"):
        assert conf[key]
    assert set(conf["check"]) == {"requests", "logit_margin", "logit_rms"}
    assert {"logit_margin", "logit_rms"} <= set(conf["check_why"])
    # the assumed initialisation: norm scales away from zero on both
    # sides, a peaked softmax, steps small enough for the state to
    # remember, branches small enough for bf16's rounding to stay small
    assert set(conf["init"]) == {"norm", "qk_norm", "delta_norm", "dt",
                                 "matrix_std"}
    assert conf["init"]["norm"] == [0.25, 0.75, "signed"]
    assert conf["init"]["dt"][1] <= 0.1 < 1.0
    assert 0 < conf["init"]["matrix_std"] <= 0.02
    assert sum(a.startswith("init.") for a in conf["assumed"]) == 4


def test_two_seeds_offer_the_same_prompts_at_the_same_times():
    t = _json(BENCH, "traffic", CELL + ".json")
    a = traffic_gen.make_requests(t, 1, 37984, 60)
    b = traffic_gen.make_requests(t, 4900000000, 37984, 60)
    assert [(len(r.prompt), r.max_new, r.due_s, r.tenant) for r in a] == \
        [(len(r.prompt), r.max_new, r.due_s, r.tenant) for r in b]
    assert a[0].prompt != b[0].prompt           # the seed makes the ids
    lens = np.asarray([len(r.prompt) for r in a])
    assert lens.min() >= 1088 and lens.max() <= 24640
    outs = np.asarray([r.max_new for r in a])
    assert outs.min() >= 128 and outs.max() <= 2048
    assert max(max(r.prompt) for r in a) < 37984    # ids from the slice
    assert (t["tenants"], t["prefix_tokens"], t["ramp_s"]) == (8, 64, 20)
    assert t["driver"] == "open_loop_qwen3next"
    assert t["rate_rps"] == pytest.approx(0.8 * t["knee_rps"])
    # a prompt and its answer fit the served positions
    conf = _json(BENCH, "configs", CONFIG + ".json")
    assert 24640 + 2048 <= conf["max_position_embeddings"]
    assert 24640 + 2048 <= max(qwen3next_cell.SEQ_BUCKETS)


def test_the_driver_names_the_program_s_new_modules_at_its_top():
    with open(os.path.join(BENCH, "drivers",
                           "open_loop_qwen3next.py")) as f:
        text = f.read()
    head = text.split("def run")[0]
    for module in ("flexflow_tpu.models.qwen3_next",
                   "flexflow_tpu.ops.gated_delta",
                   "flexflow_tpu.ops.gated_attention"):
        assert f"import {module}" in head


@pytest.mark.parametrize("name", ["no_correction", "plain_norm_scale",
                                  "no_output_gate"])
def test_a_planted_fault_is_in_the_program_and_is_taken_out_again(name):
    import check_qwen3next_logits as chk
    from flexflow_tpu.ops import gated_attention as GA
    from flexflow_tpu.ops import gated_delta as GD
    from flexflow_tpu.serve.arch import Qwen3Next
    sound = (GD._token, GD._chunk, GA.rms_norm0,
             Qwen3Next.__dict__["attn_gate"])
    with chk.faulty_program(name):
        now = (GD._token, GD._chunk, GA.rms_norm0,
               Qwen3Next.__dict__["attn_gate"])
        assert now != sound
    assert (GD._token, GD._chunk, GA.rms_norm0,
            Qwen3Next.__dict__["attn_gate"]) == sound


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse-cpu", "--seed", "4900000007", "--seconds", "3"],
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
    assert 0 < numbers["slots_held"] < numbers["slots_routed"]
    assert numbers["expert_dropped"] == 0
    assert numbers["expert_load_max_over_mean"] >= 1.0
    engine = json.loads(next(
        ln for ln in lines if ln.startswith("# engine: "))[10:])
    # the rehearsal's key dimension of 16 keeps the delta rule's twin;
    # at the published 128 the cell's engine line says "pallas"
    assert engine["kinds"] == "dddf" and engine["delta_impl"] == "jnp"
