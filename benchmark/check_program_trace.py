#!/usr/bin/env python3
"""Check lib/program_trace.py: its wire decoder against JAX's own
reader on both recorded traces in testdata/, every reduction on
testdata/scoped_trace.xplane.pb (made on one v5e chip by
testdata/record_scoped_trace.py: six steps of a small train step under
named scopes and the program's phase spans), and on hand-made
operations and spans for what that trace does not have (layers, a
root scope, nested child spans, a step cut by the window's edge).

    python3 benchmark/check_program_trace.py      # exit 0 = all hold

Needs no accelerator: reading a trace needs only JAX's ProfileData."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from lib import program_trace as pt  # noqa: E402
from lib import trace_reduce as tr   # noqa: E402

SMALL = os.path.join(HERE, "testdata", "small_trace.xplane.pb")
SCOPED = os.path.join(HERE, "testdata", "scoped_trace.xplane.pb")


def close(a, b, tol=1e-9):
    return abs(a - b) <= tol


def check_decoder(path):
    """Plane, line and event for event: the names, starts and
    durations `jax.profiler.ProfileData` shows (it rounds to whole
    nanoseconds)."""
    from jax.profiler import ProfileData
    planes = pt.decode(path)
    theirs = list(ProfileData.from_file(path).planes)
    assert [p["name"] for p in planes] == [p.name for p in theirs]
    n = 0
    for mine, ref in zip(planes, theirs):
        ref_lines = list(ref.lines)
        assert [ln["name"] for ln in mine["lines"]] == \
            [ln.name for ln in ref_lines], mine["name"]
        for ln, rl in zip(mine["lines"], ref_lines):
            ref_events = list(rl.events)
            assert len(ln["events"]) == len(ref_events), ln["name"]
            for e, r in zip(ln["events"], ref_events):
                assert e["name"] == r.name
                assert abs(e["start_ps"] * 1e-3 - r.start_ns) < 1.0
                assert abs(e["duration_ps"] * 1e-3 - r.duration_ns) < 1.0
                n += 1
    return n


def check_small():
    """The trace PR 23 recorded holds what ProfileData does not show."""
    assert check_decoder(SMALL) == 229
    t = pt.load(SMALL)
    ops = t["devices"][0]
    assert len(ops) == 18
    fus = [o for o in ops if "convolution_reduce_fusion" in o.name]
    assert len(fus) == 6
    for o in fus:
        assert o.tf_op == "jit(<lambda>)/dot_general", o.tf_op
        assert o.flops == 4298113024.0 and o.bytes_accessed == 6291458.0
    # a program without scopes: every operation is unscoped, and the
    # two reductions agree on the window and on the names
    assert t["phases"] == []
    sc = pt.by_scope(t)
    red = tr.reduce(tr.load(SMALL))
    assert sc["phases"] == {} and sc["scoped_s"] == 0.0
    assert not sc["program"]
    # (ProfileData rounds each start and duration to whole nanoseconds)
    assert close(sc["window_s"], red["window_s"], 2e-9)
    assert close(sc["unscoped_s"], sum(red["by_name"].values()),
                 2e-9 * len(ops))
    assert close(sc["unscoped"]["convolution_reduce_fusion"],
                 red["by_name"]["convolution_reduce_fusion"],
                 2e-9 * len(fus))


def check_paths():
    sp = pt.scope_path
    assert sp("") == ((), False)
    assert sp("jit(<lambda>)/dot_general") == ((), False)
    assert sp("jit(_mixed_impl)/serve_step/layer3/attn/pallas_call") == \
        (("serve_step", "layer3", "attn"), False)
    assert sp("jit(_mixed_impl)/serve_step/layer0/qkv/...e,ehd->...hd/"
              "dot_general") == \
        (("serve_step", "layer0", "qkv", "...e,ehd->...hd"), False)
    assert sp("jit(_mixed_impl)/serve_step/embed/jit(_take)/gather") == \
        (("serve_step", "embed"), False)
    assert sp("jit(_step_body)/jvp(layer0_attn)/dot_general") == \
        (("layer0_attn",), False)
    assert sp("jit(_step_body)/transpose(jvp(layer0_attn))/dot_general") \
        == (("layer0_attn",), True)
    assert sp("jit(f)/transpose(jvp(block/proj))/jit(_where)/select_n") \
        == (("block", "proj"), True)
    assert sp("jit(_step_body)/optimizer/mul") == (("optimizer",), False)
    # what the file holds: `<name stack>:<type>`, several joined by `;`
    assert pt._TF_OP_REST.sub("", "jit(f)/a/reshape:;jit(f)/b/squeeze:") \
        == "jit(f)/a/reshape"
    assert pt._TF_OP_REST.sub("", "jit(f)/a/dot_general:") == \
        "jit(f)/a/dot_general"
    assert pt._TF_OP_REST.sub("", "") == ""
    ph = pt.phase_of
    assert ph(("serve_step", "layer3", "attn")) == ("attn", "layer3")
    assert ph(("serve_step", "head")) == ("head", None)
    assert ph(("layer0_ff1",)) == ("ff1", "layer0")
    assert ph(("layer7",)) == ("layer7", "layer7")
    assert ph(("lm_head",)) == ("lm_head", None)
    assert ph(()) == ("", None)


def _op(name, a, b, tf_op, flops=0.0, nbytes=0.0):
    return pt.Op(name, a, b, tf_op, flops, nbytes)


def check_synthetic():
    # window [0, 10). Two steps of a "serve" program on chip 0:
    #   step 0, span [0.5, 5): kv_write 1-2, attn 2-4 (layer0)
    #   step 1, span [5.5, 11): kv_write 6-7, attn 7-9, then two of the
    #           compiler's operations with no name stack: a copy 9-9.5
    #           of what the scatter made, a slice 9.5-9.6 of a
    #           parameter — cut by the window's edge
    # and an operation before any span (0-0.25) under `embed`.
    S = "jit(_mixed_impl)/serve_step/"
    ops = [_op("%fusion.9 = x fusion(), kind=kLoop", 0.0, 0.25,
               S + "embed/add", 10.0, 100.0),
           _op("%scatter.1 = x", 1.0, 2.0, S + "layer0/kv_write/scatter",
               0.0, 4e9),
           _op("%paged = x custom-call()", 2.0, 4.0,
               S + "layer0/attn/pallas_call", 8e12, 2e9),
           _op("%scatter.1 = x", 6.0, 7.0, S + "layer0/kv_write/scatter",
               0.0, 4e9),
           _op("%paged = x custom-call()", 7.0, 9.0,
               S + "layer0/attn/pallas_call", 8e12, 2e9),
           _op("%copy.4 = x copy(x %scatter.1)", 9.0, 9.5, ""),
           _op("%slice-start.2 = x slice-start(x %param.3)", 9.5, 9.6, "")]
    span = pt.Span
    phases = [span("serve_step", 0.5, 5.0, {}),
              span("pack", 0.5, 0.9, {}),
              span("dispatch", 0.9, 1.5, {"step": 0, "kv_bytes": 1e9}),
              span("fetch", 1.5, 4.5, {}),
              span("emit", 4.5, 5.0, {}),
              span("serve_step", 5.5, 11.0, {}),
              span("dispatch", 5.6, 6.5, {"step": 1, "kv_bytes": 3e9}),
              span("fetch", 6.5, 10.5, {})]
    t = {"devices": {0: ops}, "phases": phases,
         "bench": [span("window", 0.0, 10.0, {}),
                   span("step", 0.4, 5.1, {})]}
    sc = pt.by_scope(t)
    assert sc["program"] and close(sc["window_s"], 10.0)
    # JAX's own names (an einsum's formula, a kernel's name) make
    # phases, but not a scoped program: the readers then read nothing
    own = dict(t, devices={0: [
        _op("%f.1 = x", 1.0, 2.0, "jit(_mixed_impl)/...e,ehd->...hd/dot"),
        _op("%k.1 = x", 2.0, 3.0, "jit(_mixed_impl)/paged_ragged_v2/x")]})
    assert set(pt.by_scope(own)["phases"]) == {"...e,ehd->...hd",
                                               "paged_ragged_v2"}
    assert not pt.by_scope(own)["program"]
    assert close(sc["phases"]["attn"]["seconds"], 4.0)
    assert close(sc["phases"]["kv_write"]["seconds"], 2.0)
    assert close(sc["phases"]["embed"]["seconds"], 0.25)
    assert set(sc["unscoped"]) == {"copy", "slice-start"}
    assert close(sc["unscoped"]["copy"], 0.5)
    assert close(sc["unscoped_s"], 0.6) and close(sc["scoped_s"], 6.25)
    # the copy goes to the phase that made its operand; the slice of a
    # parameter has no scoped neighbour and stays with nobody
    assert sc["attributed"] == {"copy": {"kv_write": 0.5}}
    assert close(sc["phases"]["kv_write"]["attributed_s"], 0.5)
    assert close(pt.seconds_matching(sc, "^kv_write$"), 2.0)
    assert close(pt.seconds_matching(sc, "^kv_write$", True), 2.5)
    assert sc["phases"]["attn"]["ops"] == {"paged": 4.0}
    assert close(sc["layers"][("attn", "layer0")], 4.0)
    assert ("embed", None) not in sc["layers"]
    # XLA's counts are per execution: two executions, 2 x 8e12 flops
    # in 4 s = 4 TFLOP/s, 2 x 4e9 bytes in 2 s = 4 GB/s
    assert close(sc["phases"]["attn"]["flops"] / 4.0, 4e12, 1.0)
    assert close(sc["phases"]["kv_write"]["bytes"] / 2.0, 4e9, 1.0)
    assert close(pt.seconds_matching(sc, "^(attn|kv_write)$"), 6.0)
    # whole steps: only the first lies wholly in the window
    whole = pt.whole_steps(t, "serve_step")
    assert [s.start for s in whole] == [0.5]
    assert close(pt.seconds_in(t, "^attn$", whole), 2.0)
    assert close(pt.seconds_in(t, "^attn$", [phases[5]]), 2.0)
    # the program's count per device second, over whole steps: step 0
    # carries 1e9 bytes and 2 s under `attn`
    assert close(pt.counted_rate(t, "serve_step", "dispatch", "kv_bytes",
                                 "^attn$"), 5e8, 1e-3)
    assert pt.counted_rate(t, "serve_step", "fetch", "kv_bytes",
                           "^attn$") is None
    assert pt.counted_rate(t, "serve_step", "dispatch", "kv_bytes",
                           "^head$") is None
    # idle inside the steps, by innermost child span:
    #  step 0 [0.5, 5): idle 0.5-1 (pack 0.4, dispatch 0.1), 4-5
    #          (fetch 0.5, emit 0.5)
    #  step 1 [5.5, 10) clipped: idle 5.5-6 (root 0.1, dispatch 0.4),
    #          9.6-10 (fetch 0.4)
    g = pt.host_gaps(t, "serve_step")
    assert len(g["idle_s"]) == 1 and close(g["idle_s"][0], 1.5)
    want = {"pack": 0.4, "dispatch": 0.5, "fetch": 0.9, "emit": 0.5,
            "serve_step": 0.1}
    assert set(g["by_phase"]) == set(want), g["by_phase"]
    for k, v in want.items():
        assert close(g["by_phase"][k], v, 1e-9), (k, g["by_phase"][k])
    assert close(g["total_s"], 2.4)
    # trace_reduce names a gap whole, by its middle: the gaps 0.25-1
    # (middle 0.625, in step 0), 4-6 (middle 5.0: between the steps, in
    # neither) and 9.6-10 (in step 1) -> 0.75 + 0.4
    assert close(g["whole_gaps_s"], 1.15)
    # two chips: seconds are the mean over the chips used
    t2 = dict(t, devices={0: ops, 1: ops[:3]})
    assert close(pt.by_scope(t2)["phases"]["attn"]["seconds"], 3.0)
    assert close(pt.by_scope(t2, chips=1)["phases"]["attn"]["seconds"],
                 4.0)
    # a window cut through an operation counts its part, and the same
    # part of XLA's counts
    t3 = dict(t, bench=[span("window", 0.0, 8.0, {})])
    sc3 = pt.by_scope(t3)
    assert close(sc3["phases"]["attn"]["seconds"], 3.0)
    assert close(sc3["phases"]["attn"]["flops"], 1.5 * 8e12, 1.0)


def check_readers():
    """The readers on hand-made runs: a number where the program wrote
    scopes and spans, None where it did not (the parent commit)."""
    from readers import host_gap, kernel_hbm_share, scope_share
    run = {"trace": {}, "device_kind": "TPU v5 lite"}
    assert scope_share.read(run, phases="^attn$") is None
    assert host_gap.read(run, root="serve_step") is None
    small = {"trace": {"trace_file": SMALL, "chips": 1},
             "device_kind": "TPU v5 lite"}
    assert scope_share.read(small) is None          # no scope at all
    assert scope_share.read(small, phases="^attn$") is None
    assert host_gap.read(small, root="train_step") is None
    assert kernel_hbm_share.read(
        small, root="serve_step", span="dispatch", arg="kv_bytes",
        phases="^attn$") is None


def check_scoped():
    """The recorded trace: six steps of record_scoped_trace.py's
    program on one v5e chip."""
    from readers import host_gap, kernel_hbm_share, scope_share
    sys.path.insert(0, os.path.join(HERE, "testdata"))
    from record_scoped_trace import KV_BYTES
    assert check_decoder(SCOPED) > 100
    t = pt.load(SCOPED)
    assert list(t["devices"]) == [0]
    names = [s.name for s in t["phases"]]
    assert names.count("train_step") == 6 and names.count("fetch") == 6
    disp = [s for s in t["phases"] if s.name == "dispatch"]
    assert [s.args for s in disp] == [
        {"step": i, "kv_bytes": KV_BYTES + i} for i in range(6)]
    lo, hi = pt.window_of(t)
    red = tr.reduce(tr.load(SCOPED))
    assert close(hi - lo, red["window_s"], 2e-9)
    n_ops = len(t["devices"][0])
    sc = pt.by_scope(t)
    # every phase the program named is there, forward and backward of
    # the differentiated ones apart
    # apart — except `loss`: XLA fused all of it into fusions rooted in
    # the other two, and a fusion carries one name, its root's
    assert sc["program"]
    assert set(sc["phases"]) == {"block", "optimizer"}, sc["phases"].keys()
    assert set(sc["phases"]["optimizer"]["ops"]) == \
        {"multiply_subtract_fusion"}
    assert sc["phases"]["block"]["forward_s"] > 0
    assert sc["phases"]["block"]["backward_s"] > 0
    assert sc["phases"]["optimizer"]["backward_s"] == 0
    # scoped + unscoped = all device time in the window, by another
    # route: trace_reduce's sum over operation names
    assert close(sc["scoped_s"] + sc["unscoped_s"],
                 sum(red["by_name"].values()), 2e-9 * n_ops)
    # the matmuls under `block` are the matmul-rooted fusions
    # trace_reduce finds by XLA's name (three dots forward + backward
    # of two 1024^3 products: 2 forward, 4 backward... as XLA fused)
    assert sc["phases"]["block"]["flops"] > 0
    tflops = sc["phases"]["block"]["flops"] / \
        sc["phases"]["block"]["seconds"] / 1e12
    assert 1.0 < tflops < 197.0, tflops
    # idle by phase span: the steps' gaps lie in `dispatch` and
    # `fetch`; all idle inside the steps is owned by some span, and
    # idle in train_step + idle in the sleeps = the window's idle
    g = pt.host_gaps(t, "train_step")
    assert len(g["idle_s"]) in (5, 6)
    assert set(g["by_phase"]) <= {"train_step", "dispatch", "fetch"}
    # the gaps whose middle a step covers, each whole, are the gaps
    # trace_reduce names by a span — here by none, the recorder wrote
    # no `bench:` span around a step; the rest fall in the sleeps. Cut
    # at the spans' edges the steps hold less: the device's clock runs
    # about 1.4 ms ahead of the host's in this trace, so part of each
    # gap lies under the sleep before its step
    assert close(g["whole_gaps_s"], dict(red["idle_gaps"])["no_span"],
                 1e-6)
    assert 0 < g["total_s"] < g["whole_gaps_s"]
    run = {"trace": {"trace_file": SCOPED, "chips": 1},
           "device_kind": "TPU v5 lite"}
    share = scope_share.read(run, phases="^block$", scale=100.0)
    assert close(share, 100 * sc["phases"]["block"]["seconds"]
                 / sc["window_s"], 1e-9)
    un = scope_share.read(run, scale=100.0)
    assert close(un, 100 * sc["unscoped_s"] / sc["window_s"], 1e-9)
    ms = host_gap.read(run, root="train_step", scale=1000.0)
    assert 0.0 < ms < 5.0, ms
    # a step here is 0.3 ms of device work, and the device's clock runs
    # 1.4 ms ahead: no operation lies inside its own step's span, so
    # the program's count has no device second to be divided by
    assert pt.seconds_in(t, "^block$", pt.whole_steps(t, "train_step")) \
        == 0.0
    assert kernel_hbm_share.read(run, root="train_step", span="dispatch",
                                 arg="kv_bytes", phases="^block$") is None


if __name__ == "__main__":
    check_paths()
    check_synthetic()
    check_small()
    check_readers()
    check_scoped()
    print("program trace: all checks hold")
