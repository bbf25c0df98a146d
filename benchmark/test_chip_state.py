"""The chip-state probe, the gate and the longer document list, checked
without the chip:

    python3 -m pytest benchmark/test_chip_state.py -q

(PR 42 could add no file under tests/: PERF.md, open question 27.)"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
from lib import chip_state as cs  # noqa: E402
from lib import traffic_gen  # noqa: E402


def test_the_probe_s_operations_and_bytes_by_hand():
    # 8192^3 = 549,755,813,888 multiply-adds a product, 44 in a row
    assert cs.matmul_flops() == 2 * 549_755_813_888 * 44
    assert cs.matmul_flops(2, 3) == 2 * 8 * 3
    # 256 MiB read and written, 160 times
    assert cs.sweep_traffic_bytes() == 2 * 268_435_456 * 160
    # two operands and a product of 128 MiB, or the swept array; the
    # compiled chain plans one more product: 512 MiB (compiled for a
    # v5e, PR 42)
    assert cs.held_bytes() == 402_653_184
    assert cs.held_bytes() + 8192 * 8192 * 2 < 0.6e9
    # at 185 TFLOP/s the chain outlasts the host clock's 250 ms
    assert cs.matmul_flops() / 185e12 > 0.25


def test_the_probe_runs_at_a_tiny_size_and_frees_what_it_took():
    import jax
    before = len(jax.live_arrays())
    r = cs.probe(n=128, chain_len=2, nbytes=2 ** 16, sweeps=2, repeats=2)
    assert r["tflops"] == max(r["tflops_all"]) > 0 and r["gbps"] > 0
    assert len(r["tflops_all"]) == 2 and r["probe_s"] > 0
    assert len(jax.live_arrays()) == before


def test_step_ms_thirds_on_hand_made_steps():
    # window [10, 40): thirds of 10 s; a step counts where it STARTS
    starts = [9.9, 10.0, 12.0, 19.9, 20.0, 25.0, 30.0, 39.9, 40.0]
    secs = [9.0, 0.1, 0.3, 0.2, 0.4, 0.6, 0.5, 0.7, 9.0]
    assert cs.step_ms_thirds(starts, secs, 10.0, 40.0) == pytest.approx(
        [200.0, 500.0, 600.0])
    assert cs.step_ms_thirds([12.0], [0.1], 10.0, 40.0) == [
        pytest.approx(100.0), None, None]
    assert cs.step_ms_thirds([], [], 0.0, 3.0) == [None, None, None]


def _reading(slow):
    """E3's two states: the chain 0.8 % and the sweep 1.3 % under."""
    return {"tflops": 173.6 * (0.992 if slow else 0.9995), "tflops_all": [],
            "gbps": 652.0 * (0.987 if slow else 1.0), "sync_us": 100.0,
            "probe_s": 1.0}


def test_a_rehearsal_keeps_no_reading():
    chip = cs.ChipState(rehearse=True, take=lambda **size: _reading(True))
    for point in cs.POINTS:
        chip.take(point)
    s = chip.summary([1.0, 2.0, 3.0])
    assert s["tflops"] == [None] * 3 and s["gbps"] == [None] * 3
    assert s["step_ms_thirds"] == [None] * 3 and s["rehearsal"] is True
    assert s["state"] == "not_measured"
    assert chip.tflops_of_window() is None


def _asks(documents):
    with open(os.path.join(HERE, "traffic", "docqa-closed8.json")) as f:
        t = json.load(f)
    t["documents"] = documents
    return t, traffic_gen.make_document_asks(t, 4200000042, 50272, documents)


def test_the_longer_document_list_starts_with_the_old_one():
    """S0: `documents` 160 -> 320 leaves the first 480 asks as they were,
    token for token (lengths are drawn in blocks of `length_block`)."""
    t, long = _asks(320)
    assert t["length_block"] == 160 and len(long) == 960
    _, short = _asks(160)
    assert len(short) == 480
    for a, b in zip(short, long):
        assert (a.prompt, a.max_new, a.doc, a.ask) == (
            b.prompt, b.max_new, b.doc, b.ask)
    # block 0 is drawn by the calls the whole list had before PR 42
    fixed = traffic_gen._rng(t["sizes_seed"], 3)
    doc_len = traffic_gen._lengths(fixed, 160, t["document"])
    q_len = traffic_gen._lengths(fixed, 480, t["question"]).reshape(160, 3)
    out = traffic_gen._lengths(fixed, 480, t["output"]).reshape(160, 3)
    for r in short:
        assert len(r.prompt) == doc_len[r.doc] + q_len[r.doc, r.ask]
        assert r.max_new == out[r.doc, r.ask]
    # the second block differs from the first and keeps the file's limits
    lens = [len(r.prompt) for r in long[480:]]
    assert lens != [len(r.prompt) for r in long[:480]]
    assert min(lens) >= 768 + 32 and max(lens) <= 1792 + 128
    # without the key a list is drawn at once, as before
    t.pop("length_block")
    whole = traffic_gen.make_document_asks(t, 4200000042, 50272, 160)
    assert [r.prompt for r in whole] == [r.prompt for r in short]


def test_every_new_metric_has_its_files_and_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    probes = {n: m for n, m in per_layer.items()
              if n.startswith("chip_probe_tflops.")}
    # one a cell, each moving the end-to-end metric its cell reports
    assert sorted(m["workloads"][0] for m in probes.values()) == sorted(
        w["name"] for w in bench["workloads"])
    for name, m in probes.items():
        assert m["layer"] == "chip" and len(m["workloads"]) == 1
        assert m["workloads"][0] in e2e[m["moves"]]["workloads"]
        with open(os.path.join(HERE, "metrics", name + ".json")) as f:
            assert json.load(f) == {"reader": "value",
                                    "args": {"key": "chip_probe_tflops"}}
    for name, reader, phases in (
            ("experts_share.olmoe", "scope_share", "^experts$"),
            ("expert_hbm_share.olmoe", "kernel_hbm_share", "^experts$")):
        with open(os.path.join(HERE, "metrics", name + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] == reader and spec["args"]["phases"] == phases
        assert per_layer[name]["workloads"] == ["olmoe-chat"]
    assert per_layer["attn_short_share.phi"]["workloads"] == [
        "phi4flash-reason"]


def test_a_cpu_rehearsal_prints_chip_state_and_no_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "pretrain-1chip", "--seed", "4200000077", "--seconds", "2",
         "--trace", "0", "--rehearse-cpu"],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["metrics"] == {} and last["rehearsal"] is True
    state = [json.loads(ln.split(": ", 1)[1]) for ln in lines
             if ln.startswith("# chip_state: ")]
    assert len(state) == 1 and state[0]["rehearsal"] is True
    assert state[0]["tflops"] == [None] * 3
    assert len(state[0]["at_s"]) == 3


@pytest.mark.parametrize("before, setup_s, starts, state", [
    # fast: no second start
    (["fast"], 27.0, 1, "fast"),
    # slow, slow, fast: two starts given up, 54 s of the 60
    (["slow", "slow", "fast"], 27.0, 3, "fast"),
    # slow every time: the third start would pass the budget (81 s), so
    # it runs its window and says so
    (["slow"] * 4, 27.0, 3, "slow"),
    # a shorter set-up buys a fourth start (19, 38, 57 s given up)
    (["slow"] * 4, 19.0, 4, "slow"),
    # a cold run's set-up is over the budget alone: it is never given up
    (["slow"], 96.0, 1, "slow"),
    # the readings around the window disagree: reported as it is
    (["fast", "then_slow"], 27.0, 1, "mixed"),
    (["slow", "slow", "slow", "then_fast"], 27.0, 3, "mixed"),
])
def test_the_gate_on_fake_probe_sequences(before, setup_s, starts, state):
    """run.py's parent over children whose probes read a given
    sequence: how many are started, and what the one that runs its
    window reports."""
    import run as harness
    now = [1000.0]
    script = list(before)
    ran = []

    def child(cmd):
        start = int(cmd[cmd.index("--start") + 1])
        spent = float(cmd[cmd.index("--gate-spent-s") + 1])
        assert cmd[2:4] == ["--workload", "x"]
        slow = script.pop(0) == "slow"
        after = slow
        if script and script[0].startswith("then_"):
            after = script.pop(0) == "then_slow"
        chip = cs.ChipState(t_start=0.0, start=start, spent_s=spent,
                            take=lambda: None)
        t_child = now[0]
        # the child's own clock: at_s is taken from perf_counter, so the
        # readings are placed by hand
        chip.readings.append(("start", 1.0, _reading(False)))
        now[0] = t_child + setup_s
        r = _reading(slow)
        chip.readings.append(("before_ramp", setup_s, r))
        if cs.gives_up(cs.is_slow(r, chip.readings[0][2]), setup_s, spent):
            return cs.EXIT_SLOW
        now[0] += 100.0
        chip.readings.append(("after_drain", setup_s + 100.0,
                              _reading(after)))
        ran.append(chip.summary([110.0, 110.5, 111.0]))
        return 0

    rc = harness.parent(["--workload", "x"], run=child,
                        clock=lambda: now[0])
    assert rc == 0 and len(ran) == 1
    assert ran[0]["attempts"] == starts and ran[0]["state"] == state
    assert ran[0]["gate_spent_s"] == pytest.approx((starts - 1) * setup_s)
    assert ran[0]["gate_spent_s"] <= cs.GATE_BUDGET_S


def test_take_gives_the_window_up_only_before_the_ramp():
    seq = [_reading(False), _reading(True)]
    chip = cs.ChipState(t_start=__import__("time").perf_counter(),
                        take=lambda: seq.pop(0))
    chip.take("start")
    with pytest.raises(cs.SlowChip):
        chip.take("before_ramp")
    assert chip.summary()["state"] == "slow"
    # with the budget spent the same reading lets the window run
    seq = [_reading(False), _reading(True), _reading(True)]
    chip = cs.ChipState(t_start=__import__("time").perf_counter(),
                        spent_s=cs.GATE_BUDGET_S, take=lambda: seq.pop(0))
    for point in cs.POINTS:
        chip.take(point)
    assert chip.summary([1.0, 1.0, 1.0])["state"] == "slow"
    assert chip.tflops_of_window() == _reading(True)["tflops"]
    # a traced run keeps its window and reports what it read
    seq = [_reading(False), _reading(True), _reading(True)]
    chip = cs.ChipState(t_start=__import__("time").perf_counter(),
                        gate=False, take=lambda: seq.pop(0))
    for point in cs.POINTS:
        chip.take(point)
    assert chip.summary()["state"] == "slow"
    # thirds that disagree make a window mixed whatever the probes say
    assert cs.window_state([False, False], [110.0, 110.5, 116.0]) == "mixed"
    assert cs.window_state([False, False], [110.0, 111.5, None]) == "fast"


def test_the_parent_passes_the_child_s_output_and_exit_code_through(
        tmp_path):
    """The real parent over a real child: the last stdout line and the
    exit code are the child's own (here: a workload that does not
    exist, which the child refuses before it touches JAX)."""
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "no-such-cell"], capture_output=True, text=True, cwd=ROOT,
        timeout=120)
    assert out.returncode == 1 and out.stdout == ""
    assert "no workload 'no-such-cell'" in out.stderr
    # and a child's own code and lines, through run_child
    import run as harness
    code = ("import sys; print('# progress'); print('{\"ok\": 1}'); "
            "sys.exit(7)")
    got = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, %r); import run; "
         "sys.exit(run.run_child([sys.executable, '-c', %r]))"
         % (HERE, code)], capture_output=True, text=True, timeout=120)
    assert got.returncode == 7
    assert got.stdout.splitlines() == ["# progress", '{"ok": 1}']
    assert harness.parent.__defaults__[0] is harness.run_child
