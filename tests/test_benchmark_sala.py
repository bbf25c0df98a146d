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


def assert_cell_and_its_entries(cell_name, config, named):
    """What a serving cell's place in BENCHMARK.json has to keep, however
    many metrics, files and later cells the benchmark has: the cell
    with its configuration on one chip, `tpot_p50_ms.olmoe` as its one
    end-to-end metric beside `setup_s`, every entry that lists it moving
    a metric it reports and read by a metric file, and the entries
    `named` still listing it. (That each reader reads nothing from an
    empty run is the parametrised test above, a case an entry.)"""
    bench = _json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    assert (cell["config"], cell["chips"], cell["traffic"]) == (
        config, 1, cell_name)
    assert config in [c["name"] for c in bench["configs"]]
    assert len(cell["why"]) <= 200
    e2e = [m["name"] for m in bench["end_to_end"]
           if cell_name in m.get("workloads", ())]
    assert e2e == ["tpot_p50_ms.olmoe"]
    reports = set(e2e) | {m["name"] for m in bench["end_to_end"]
                          if "workloads" not in m}
    assert "setup_s" in reports
    layer = [m for m in bench["per_layer"]
             if cell_name in m.get("workloads", ())]
    for m in layer:
        assert m["moves"] in reports, m["name"]
        assert os.path.exists(os.path.join(
            BENCH, "metrics", m["name"] + ".json")), m["name"]
    assert set(named) <= {m["name"] for m in layer}
    return layer


# the entries the cell was put on (PR 45): the step, its host side, the
# paged kernel and the request's waits, as `olmoe-chat` reads them
EIGHTEEN = [
    "compiles_in_window.olmoe", "step_ms.olmoe", "tpot_p95_ms.olmoe",
    "ttft_p50_ms.olmoe", "lane_occupancy.olmoe", "attn_kernel_share.olmoe",
    "kv_write_share.olmoe", "unscoped_share.olmoe", "step_host_ms.olmoe",
    "queue_wait_p95_ms.olmoe", "ttft_p95_ms.olmoe",
    "attn_grid_live_share.olmoe", "attn_row_fill.olmoe",
    "sample_live_share.olmoe", "fetch_host_ms.olmoe",
    "upload_host_ms.olmoe", "dispatch_fetch_host_ms.olmoe",
    "chip_probe_tflops.olmoe"]


def test_the_cell_keeps_its_place_and_the_eighteen_still_list_it():
    layer = assert_cell_and_its_entries(CELL, CONFIG, EIGHTEEN)
    # each was `olmoe-chat`'s before it was this cell's
    assert all("olmoe-chat" in m["workloads"] for m in layer
               if m["name"] in EIGHTEEN)


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
