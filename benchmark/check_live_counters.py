#!/usr/bin/env python3
"""Check the readers of the step's live counts and of the host's gaps
by phase — readers/span_ratio.py, readers/kernel_time_per_count.py,
readers/host_gap_phase.py — on hand-made operations and spans, and
every metric file that names one of them on runs with nothing to read.

    python3 benchmark/check_live_counters.py      # exit 0 = all hold

Needs no accelerator."""

import glob
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from lib import program_trace as pt  # noqa: E402
from readers import (host_gap_phase, kernel_time_per_count,  # noqa: E402
                     span_ratio)

SMALL = os.path.join(HERE, "testdata", "small_trace.xplane.pb")
READERS = ("span_ratio", "kernel_time_per_count", "host_gap_phase")


def close(a, b, tol=1e-9):
    return abs(a - b) <= tol


def _trace(counted=True):
    """Window [0, 10), three steps of a serving program on chip 0:
      A [0.5, 3): the full list's call 1.2-2.0, the window list's
                  2.0-2.2, a reshape OF the call's result 2.2-2.4
      B [3, 6):   the calls 3.5-4.5 and 4.5-4.9
      C [7, 11):  cut by the window's edge; the call 8-9
    `counted` False: the spans of a program that counts nothing yet."""
    def op(name, a, b):
        return pt.Op(name, a, b, "jit(_mixed_impl)/serve_step/layer0/"
                     "attn/pallas_call", 0.0, 0.0)

    full = ('%paged_ragged_v2.1 = bf16[18,4,32,128]{3,2,1,0} custom-call('
            's32[657]{0} %fusion.4), custom_call_target="tpu_custom_call"')
    ring = full.replace("paged_ragged_v2.1", "paged_ragged_v2_window.2")
    back = "%reshape.3 = bf16[576,2048]{1,0} reshape(x %paged_ragged_v2.1)"
    ops = [op(full, 1.2, 2.0), op(ring, 2.0, 2.2), op(back, 2.2, 2.4),
           op(full, 3.5, 4.5), op(ring, 4.5, 4.9), op(full, 8.0, 9.0)]

    def counts(live, rows, emitters):
        return {"step": 0} if not counted else {
            "step": 0, "grid_steps": 100, "live_steps": live,
            "live_rows": rows, "lanes": 64, "emitters": emitters}

    span = pt.Span
    phases = [span("serve_step", 0.5, 3.0, {}),
              span("upload", 0.5, 0.7, {}),
              span("dispatch", 0.7, 1.0, counts(10, 40, 4)),
              span("fetch", 1.0, 2.8, {}),
              span("emit", 2.8, 3.0, {}),
              span("serve_step", 3.0, 6.0, {}),
              span("upload", 3.0, 3.4, {}),
              span("dispatch", 3.4, 3.6, counts(30, 60, 12)),
              span("fetch", 3.6, 5.8, {}),
              span("serve_step", 7.0, 11.0, {}),
              span("upload", 7.0, 7.5, {}),
              span("dispatch", 7.5, 8.0, counts(100, 3200, 64)),
              span("fetch", 8.0, 10.5, {})]
    t = {"devices": {0: ops}, "phases": phases,
         "bench": [span("window", 0.0, 10.0, {})]}
    t["gaps"] = {"serve_step": pt.host_gaps(t, "serve_step")}
    return t


def check_span_ratio():
    t = _trace()
    at = ("serve_step", "dispatch")
    # sums over the two whole steps; C's counts stay out
    assert close(span_ratio.of_trace(t, *at, "live_steps", "grid_steps"),
                 40 / 200)
    assert close(span_ratio.of_trace(t, *at, "live_rows", "live_steps"),
                 100 / 40)
    assert close(span_ratio.of_trace(t, *at, "emitters", "lanes"),
                 16 / 128)
    # an argument the span does not carry, a span the step has not
    assert span_ratio.of_trace(t, *at, "live_steps", "no_such") is None
    assert span_ratio.of_trace(t, "serve_step", "fetch", "live_steps",
                               "grid_steps") is None
    assert span_ratio.of_trace(t, "train_step", "dispatch", "live_steps",
                               "grid_steps") is None
    assert span_ratio.of_trace(_trace(counted=False), *at, "live_steps",
                               "grid_steps") is None


def check_kernel_time_per_count():
    t = _trace()
    at = ("serve_step", "dispatch", "live_steps")
    both = kernel_time_per_count.of_trace(t, *at, "^%?paged_ragged")
    assert close(both, (0.8 + 0.2 + 1.0 + 0.4) / 40)
    ring = kernel_time_per_count.of_trace(
        t, *at, "^%?paged_ragged_v2_window")
    assert close(ring, (0.2 + 0.4) / 40)
    # over the grid the device walks, live or not
    assert close(kernel_time_per_count.of_trace(
        t, "serve_step", "dispatch", "grid_steps", "^%?paged_ragged"),
        both * 40 / 200)
    # an operation's name is its whole instruction, operands and all:
    # unanchored, the reshape of the call's result counts too
    assert close(kernel_time_per_count.of_trace(t, *at, "paged_ragged"),
                 both + 0.2 / 40)
    assert kernel_time_per_count.of_trace(t, *at, "^%?no_such") is None
    assert kernel_time_per_count.of_trace(
        _trace(counted=False), *at, "^%?paged_ragged") is None
    # the metric files anchor it
    for path in glob.glob(os.path.join(HERE, "metrics", "*.json")):
        with open(path) as f:
            spec = json.load(f)
        if spec["reader"] == "kernel_time_per_count":
            assert spec["args"]["ops"].startswith("^"), path


def check_host_gap_phase():
    # idle by innermost child span, every root span that meets the
    # window, clipped to it:
    #  A: 0.5-1.2 (upload 0.2, dispatch 0.3, fetch 0.2), 2.4-3 (fetch
    #     0.4, emit 0.2)
    #  B: 3-3.5 (upload 0.4, dispatch 0.1), 4.9-6 (fetch 0.9, root 0.2)
    #  C: 7-8 (upload 0.5, dispatch 0.5), 9-10 (fetch 1.0)
    t = _trace()
    g = t["gaps"]["serve_step"]
    fetch, upload, dispatch = (
        host_gap_phase.of_trace(t, "serve_step", phase)
        for phase in ("fetch", "upload", "dispatch"))
    assert close(fetch, (0.2 + 0.4 + 0.9 + 1.0) / 3)
    assert close(upload, (0.2 + 0.4 + 0.5) / 3)
    assert close(dispatch, (0.3 + 0.1 + 0.5) / 3)
    assert close(g["total_s"], 1.3 + 1.6 + 2.0)
    assert fetch + upload + dispatch <= g["total_s"] / 3
    # a pattern sums the spans it names whole: `fetch` is not `prefetch`
    assert close(host_gap_phase.of_trace(t, "serve_step", "dispatch|fetch"),
                 dispatch + fetch)
    assert host_gap_phase.of_trace(t, "serve_step", "etch") is None
    assert host_gap_phase.of_trace(t, "serve_step", "no_such") is None
    assert host_gap_phase.of_trace(t, "train_step", "fetch") is None
    # the spans are older than the counts: a program that counts
    # nothing yet has its gaps read all the same
    assert close(host_gap_phase.of_trace(
        _trace(counted=False), "serve_step", "fetch"), fetch)


def check_metric_files():
    """Every metric file of the three readers, on a run that was not
    traced and on the trace of a program without spans: nothing, and
    no exception."""
    runs = [{"numbers": {}, "trace": {}, "device_kind": "TPU v5 lite"},
            {"numbers": {}, "trace": {"trace_file": SMALL, "chips": 1},
             "device_kind": "TPU v5 lite"}]
    seen = {}
    for path in sorted(glob.glob(os.path.join(HERE, "metrics", "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if spec["reader"] not in READERS:
            continue
        reader = importlib.import_module("readers." + spec["reader"])
        for run in runs:
            assert reader.read(run, **spec["args"]) is None, path
        seen[spec["reader"]] = seen.get(spec["reader"], 0) + 1
    assert seen == {"span_ratio": 13, "kernel_time_per_count": 4,
                    "host_gap_phase": 12}, seen


CHECKS = (check_span_ratio, check_kernel_time_per_count,
          check_host_gap_phase, check_metric_files)

if __name__ == "__main__":
    for check in CHECKS:
        check()
    print("live counters: all checks hold")
