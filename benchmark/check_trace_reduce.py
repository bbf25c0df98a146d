#!/usr/bin/env python3
"""Check lib/trace_reduce.py: on the recorded trace in testdata/ (made
on one v5e chip by testdata/record_small_trace.py: six small programs,
a host sleep between them) and on hand-made intervals for what that
trace does not have (overlap, several chips).

    python3 benchmark/check_trace_reduce.py      # exit 0 = all hold

Needs no accelerator: reading a trace needs only JAX's ProfileData."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
from lib import trace_reduce as tr  # noqa: E402


def close(a, b, tol=1e-9):
    return abs(a - b) <= tol


def check_recorded():
    t = tr.load(os.path.join(HERE, "testdata", "small_trace.xplane.pb"))
    assert list(t["devices"]) == [0], t["devices"].keys()
    ev = t["devices"][0]
    assert len(ev) == 18, len(ev)               # 6 x (start, done, fusion)
    assert [s[0] for s in t["spans"]].count("step") == 6
    lo, hi = tr.window_of(t)
    assert close(hi - lo, 0.019711539, 1e-8), hi - lo
    # independently: these operations never overlap, so busy is the
    # plain sum of what lies inside the window. The device's clock runs
    # ~1 ms ahead of the host's here, so the first program falls before
    # the window span: five of the six matmul fusions count.
    inside = [(n, max(a, lo), min(b, hi)) for n, a, b in ev
              if min(b, hi) > max(a, lo)]
    plain = sum(b - a for _, a, b in inside)
    r = tr.reduce(t)
    assert close(r["busy_s"], plain), (r["busy_s"], plain)
    assert close(r["window_s"], hi - lo)
    fus = [b - a for n, a, b in inside if "convolution_reduce_fusion" in n]
    assert len(fus) == 5 and all(23e-6 < d < 25e-6 for d in fus), fus
    assert r["device_ops"][0][0] == "convolution_reduce_fusion"
    assert close(r["device_ops"][0][1], sum(fus))
    gaps = dict(r["idle_gaps"])
    assert set(gaps) == {"step", "generator_sleep"}, gaps
    assert close(sum(gaps.values()) + r["busy_s"], r["window_s"], 1e-9)
    # six sleeps of 2 ms asked for; the last ends with the window
    assert 0.006 < gaps["generator_sleep"] < 0.013, gaps
    assert close(tr.time_matching(r, "convolution"), sum(fus))


def check_intervals():
    u = tr.union([(0, 2), (1, 3), (5, 6), (6, 7), (10, 11)])
    assert u == [(0, 3), (5, 7), (10, 11)], u
    assert tr.total(u) == 6
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 2), (3, 5)], [(1, 4)]) == [(0, 1), (4, 5)]
    assert tr.clip([(0, 2), (3, 5)], 1, 4) == [(1, 2), (3, 4)]
    assert tr.base_name("%fusion.12 = f32[8] fusion(%a), kind=kOutput, "
                        "calls=%fc") == "fusion:kOutput"
    assert tr.base_name("%all-reduce-start.3 = ...") == "all-reduce-start"


def check_synthetic():
    # two chips, window [0, 10): chip 0 computes 0-4, all-reduces 3-6
    # (1 s of it beside the compute), idles 6-10; chip 1 computes 0-8
    # with an all-reduce 2-3 wholly beside it.
    t = {"devices": {
        0: [("%fusion.1 = x fusion(), kind=kLoop", 0.0, 4.0),
            ("%all-reduce.1 = x", 3.0, 6.0)],
        1: [("%fusion.2 = x fusion(), kind=kLoop", 0.0, 8.0),
            ("%all-reduce.1 = x", 2.0, 3.0)]},
        "spans": [("window", 0.0, 10.0), ("train_batch", 0.0, 6.5),
                  ("loss_fetch", 6.5, 10.0)]}
    r = tr.reduce(t)
    assert close(r["busy_s"], (6.0 + 8.0) / 2), r
    assert dict(r["idle_gaps"]) == {"loss_fetch": 4.0}, r["idle_gaps"]
    assert close(tr.time_matching(r, "all-reduce"), (3.0 + 1.0) / 2)
    assert tr.reduce(t, chips=1)["busy_s"] == 6.0


if __name__ == "__main__":
    check_intervals()
    check_synthetic()
    check_recorded()
    print("trace reduction: all checks hold")
