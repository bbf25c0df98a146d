"""What the benchmark gained with the cell `sala-longdoc` (PR 45): its
entries and files name things that exist, the configuration holds the
catalog's numbers, its traffic is the same for every seed, the metric
files of its entries read nothing from an untraced run, and the cell
rehearses on the CPU from start to verdict."""

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

from lib import sala_cell, traffic_gen  # noqa: E402

CELL, CONFIG = "sala-longdoc", "minicpm-sala-1chip-l16"
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


def test_the_cell_is_appended_and_nothing_else_changed():
    e2e = [m["name"] for m in BENCHMARK["end_to_end"]
           if CELL in m.get("workloads", ())]
    assert e2e == ["tpot_p50_ms.olmoe"]
    # the builder's contract holds `per_layer` to 128 entries, and the
    # accepted benchmark has them: the cell adds none (PERF.md, q. 33)
    assert len(BENCHMARK["per_layer"]) == 128
    layer = [m for m in BENCHMARK["per_layer"]
             if CELL in m.get("workloads", ())]
    assert len(layer) == 18
    # appended after `olmoe-chat` (a later PR's cell comes after it)
    assert all(m["moves"] == e2e[0] and m["workloads"][:2] ==
               ["olmoe-chat", CELL] for m in layer)
    cell = next(w for w in BENCHMARK["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    assert all(len(x["why"]) <= 200 for x in BENCHMARK["workloads"]
               + BENCHMARK["configs"])


def test_the_configuration_holds_the_catalog_s_numbers_but_the_reduced():
    conf = _json(BENCH, "configs", CONFIG + ".json")
    entry = next(c for c in BENCHMARK["configs"] if c["name"] == CONFIG)
    assert conf["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "max_position_embeddings"]
    assert conf["source"] == entry["source"]
    published = {"hidden_size": 4096, "intermediate_size": 16384,
                 "head_dim": 128, "num_attention_heads": 32,
                 "num_key_value_heads": 2, "lightning_nh": 32,
                 "lightning_nkv": 32, "lightning_head_dim": 128,
                 "vocab_size": 73448, "rope_theta": 10000,
                 "rms_norm_eps": 1e-6, "scale_emb": 12, "scale_depth": 1.4,
                 "mup_denominator": 32, "dim_model_base": 256,
                 "qk_norm": True, "attn_use_rope": False,
                 "lightning_use_rope": True, "tie_word_embeddings": False}
    assert {k: conf[k] for k in published} == published
    assert len(conf["mixer_types"]) == 32
    assert conf["num_hidden_layers"] == 16
    assert conf["max_position_embeddings"] == 65536
    kept = sala_cell.layers_kept(conf)
    assert kept == list(range(0, 32, 2))
    sparse = [i for i in kept if conf["mixer_types"][i] == "minicpm4"]
    assert sparse == [0, 16, 22, 30]            # first and last are sparse
    assert sala_cell.sparse_sizes(conf) == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "topk": 64, "init_blocks": 1, "window_size": 2048,
        "dense_len": 8192}
    assert len(conf["assumed"]) >= 8
    for key in ("deployment", "memory", "system_why", "rehearsal"):
        assert conf[key]
    assert set(conf["check"]) == {"requests", "logit_margin", "logit_rms"}
    assert {"logit_margin", "logit_rms"} <= set(conf["check_why"])
    # the rehearsal still reaches past its dense_len with more visible
    # blocks than it selects
    r = conf["rehearsal"]
    t = _json(BENCH, "traffic", CELL + ".json")["rehearsal"]
    sp = r["sparse_config"]
    assert t["tail"]["min"] > sp["dense_len"]
    assert t["tail"]["max"] // sp["block_size"] > sp["topk"]
    # the assumed initialisation that makes the sparse layers count
    assert conf["init"] == {"sparse_qk_norm": 2.0}
    assert any(a.startswith("init.sparse_qk_norm 2.0")
               for a in conf["assumed"])


def test_two_seeds_offer_the_same_documents_at_the_same_times():
    t = _json(BENCH, "traffic", CELL + ".json")
    a = traffic_gen.make_requests(t, 1, 73448, 40)
    b = traffic_gen.make_requests(t, 4500000000, 73448, 40)
    assert [(len(r.prompt), r.max_new, r.due_s, r.tenant) for r in a] == \
        [(len(r.prompt), r.max_new, r.due_s, r.tenant) for r in b]
    assert a[0].prompt != b[0].prompt           # the seed makes the ids
    lens = np.asarray([len(r.prompt) for r in a])
    assert lens.min() >= 9216 and lens.max() <= 49152
    assert (lens > 8192).all()                  # every one past dense_len
    outs = np.asarray([r.max_new for r in a])
    assert outs.min() >= 64 and outs.max() <= 512
    assert t["driver"] == "open_loop_sala"
    assert t["rate_rps"] == pytest.approx(0.8 * t["knee_rps"])


def test_the_cell_rehearses_on_the_cpu():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--rehearse-cpu", "--seed", "4500000007", "--seconds", "3"],
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
    assert numbers["sparse_lanes"] > 0
    assert 0 < numbers["blocks_selected"] < numbers["blocks_visible"]
    assert numbers["state_bytes"] > 0 and numbers["selector_bytes"] > 0
    assert 0 < numbers["sparse_lanes"] <= numbers["live_lanes"]
