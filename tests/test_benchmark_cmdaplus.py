"""What the benchmark gained with the cell `cmdaplus-mixedlen` (PR 43):
its entries and files name things that exist, its traffic is the same
for every seed, and the cell rehearses on the CPU from start to
verdict."""

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

from lib import cmdaplus_cell  # noqa: E402

CELL, CONFIG = "cmdaplus-mixedlen", "command-a-plus-1chip-ep8"


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


BENCHMARK = _json(ROOT, "BENCHMARK.json")
METRICS = [m for g in ("end_to_end", "per_layer") for m in BENCHMARK[g]
           if CELL in m.get("workloads", ())]


@pytest.mark.parametrize("name", [m["name"] for m in METRICS])
def test_every_metric_of_the_cell_names_a_reader_that_exists(name):
    spec = _json(BENCH, "metrics", name + ".json")
    reader = importlib.import_module("readers." + spec["reader"])
    # the reader takes the file's arguments and reads nothing from a
    # run that has none of them
    assert reader.read({"numbers": {}, "trace": {}, "spans": None,
                        "device_kind": None}, **spec.get("args", {})) is None


# the cell's own entries (PR 45), by name: a later PR may add to them
OWN = [
    "compiles_in_window.cmda", "step_ms.cmda", "tpot_p95_ms.cmda",
    "ttft_p50_ms.cmda", "ttft_p95_ms.cmda", "queue_wait_p95_ms.cmda",
    "lane_occupancy.cmda", "step_host_ms.cmda", "unscoped_share.cmda",
    "dense_share.cmda", "chip_probe_tflops.cmda", "moe_share.cmda",
    "experts_share.cmda", "expert_hbm_share.cmda",
    "shared_experts_share.cmda", "shared_hbm_share.cmda",
    "expert_load_max_over_mean.cmda", "expert_held_share.cmda",
    "attn_kernel_share.cmda", "attn_hbm_share.cmda",
    "attn_grid_live_share.cmda", "attn_row_fill.cmda",
    "window_attn_share.cmda", "past_window_share.cmda",
    "kv_write_share.cmda", "cache_bytes_per_token.cmda"]
# ... of which these took a later cell with the same expert layer's
# scopes and counters (PR 49)
SHARED = ["moe_share.cmda", "experts_share.cmda", "expert_hbm_share.cmda",
          "shared_experts_share.cmda", "shared_hbm_share.cmda",
          "expert_load_max_over_mean.cmda", "expert_held_share.cmda"]


def test_the_cell_reports_an_end_to_end_metric_and_every_layer_metric_moves_it():
    e2e = [m["name"] for m in BENCHMARK["end_to_end"]
           if CELL in m.get("workloads", ())]
    assert e2e == ["tpot_p50_ms.olmoe"]
    layer = {m["name"]: m for m in BENCHMARK["per_layer"]
             if m["name"].endswith(".cmda")}
    assert set(OWN) <= set(layer)
    assert all(m["moves"] == e2e[0] and m["workloads"][0] == CELL
               for m in layer.values())
    assert all("qwen3next-longchat" in layer[n]["workloads"]
               for n in SHARED)
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    assert all(len(x["why"]) <= 200 for x in BENCHMARK["workloads"]
               + BENCHMARK["configs"])


def test_the_configuration_holds_the_catalog_s_numbers_but_the_reduced():
    conf = _json(BENCH, "configs", CONFIG + ".json")
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "max_position_embeddings"]
    published = {"hidden_size": 4096, "intermediate_size": 4096,
                 "head_dim": 128, "num_attention_heads": 128,
                 "num_key_value_heads": 8, "num_experts_per_tok": 8,
                 "num_shared_experts": 4, "sliding_window": 4096,
                 "rope_theta": 50000, "layer_norm_eps": 1e-5,
                 "logit_scale": 1, "first_k_dense_replace": 0}
    assert {k: conf[k] for k in published} == published
    assert len(conf["layer_types"]) == 32 and conf["router_width"] == 128
    assert cmdaplus_cell.layer_types(conf) == ["sliding_attention"] * 3 \
        + ["full_attention"]
    assert cmdaplus_cell.held(conf) == (0, 16)
    assert set(conf["check"]) == {"requests", "logit_margin", "logit_rms"}
    assert {"logit_margin", "logit_rms"} <= set(conf["check_why"])


def test_two_seeds_offer_the_same_two_class_lengths_at_the_same_times():
    t = _json(BENCH, "traffic", CELL + ".json")
    a = cmdaplus_cell.make_requests(t, 1, 32768, 400)
    b = cmdaplus_cell.make_requests(t, 4300000000, 32768, 400)
    assert [(len(r.prompt), r.max_new, r.due_s, r.tenant) for r in a] == \
        [(len(r.prompt), r.max_new, r.due_s, r.tenant) for r in b]
    assert a[0].prompt != b[0].prompt           # the seed makes the ids
    tails = np.asarray([len(r.prompt) for r in a]) - t["prefix_tokens"]
    long = tails >= t["long"]["min"]
    assert 0.22 < long.mean() < 0.38            # 30 % documents
    assert tails[long].max() <= t["long"]["max"]
    assert tails[~long].min() >= t["short"]["min"]
    assert tails[~long].max() <= t["short"]["max"]
    assert (tails[long] > 4096).all()           # every one past the window
    assert max(max(r.prompt) for r in a) < 32768 and \
        min(min(r.prompt) for r in a) >= 1


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse-cpu", "--seed", "4300000007", "--seconds", "3"],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    last = json.loads(out.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["metrics"] == {}
    assert last["failed"] == 0 and last["attempted"] > 0
    assert last["rehearsal"] is True
