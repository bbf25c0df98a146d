"""From a profiler trace (`.xplane.pb`) to numbers.

    python3 benchmark/lib/trace_reduce.py <trace.xplane.pb>   # dump

What a TPU trace holds (looked at by hand, PR 23): one plane per chip,
`/device:TPU:<n>`, whose line `XLA Ops` carries one event per executed
HLO operation (name = the HLO instruction, e.g. `fusion.123`,
`all-reduce.7`, or a Pallas kernel's custom call) and whose line `XLA
Modules` one event per executed program; `/host:CPU` holds one line per
host thread, where `jax.profiler.TraceAnnotation` spans land. All
events of one file share one clock (nanoseconds from the start of the
trace).

busy      union of the `XLA Ops` intervals of one chip, clipped to the
          window; averaged over the chips used
idle gap  a maximal interval of the window with no operation on chip 0,
          named by the innermost `bench:` span that covers its middle
by name   summed device time per operation name (digits that only
          number an instruction are dropped: `fusion.12` -> `fusion`)
"""

from __future__ import annotations

import re
import sys
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"


def union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(iv: List[Interval]) -> float:
    return sum(b - a for a, b in iv)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the (merged) intervals `a` that no interval of the
    (merged) `b` covers."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def clip(iv: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in iv
            if min(b, hi) > max(a, lo)]


def base_name(name: str) -> str:
    """`%fusion.12 = ... fusion(...), kind=kOutput, calls=...` ->
    `fusion:kOutput`: the instruction's name without its number, and
    for a fusion XLA's kind (on a TPU kOutput fusions are the ones
    rooted in a matmul/convolution, kLoop the elementwise ones)."""
    base = re.sub(r"[.\-_]?\d+$", "", name.split(" = ")[0].lstrip("%"))
    kind = re.search(r"kind=(k\w+)", name) if base == "fusion" else None
    custom = re.search(r'custom_call_target="([^"]+)"', name)
    if custom:
        base += ":" + custom.group(1)
    return base + (":" + kind.group(1) if kind else "")


def load(path: str) -> dict:
    """{'devices': {chip: [(name, start_s, end_s)]}, 'spans': [(name,
    start_s, end_s)]} from an .xplane.pb, seconds on the trace clock."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[int, list] = {}
    spans = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                devices[int(m.group(1))] = [
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans += [(e.name[len(SPAN_PREFIX):], e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[1])}


def window_of(trace: dict) -> Optional[Interval]:
    """The traced window: the `window` span the benchmark wrote around
    it, else first to last device event."""
    for name, a, b in trace["spans"]:
        if name == "window":
            return (a, b)
    ev = [e for d in trace["devices"].values() for e in d]
    if not ev:
        return None
    return (min(e[1] for e in ev), max(e[2] for e in ev))


def reduce(trace: dict, chips: Optional[int] = None, top: int = 10) -> dict:
    """Every number the benchmark takes from a trace."""
    win = window_of(trace)
    devs = sorted(trace["devices"])[:chips]
    if win is None or not devs:
        return {}
    lo, hi = win
    busy_s = []
    by_name: Dict[str, float] = {}
    for d in devs:
        ev = trace["devices"][d]
        iv = clip([(a, b) for _, a, b in ev], lo, hi)
        busy_s.append(total(union(iv)))
        for n, a, b in ev:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                key = base_name(n)
                by_name[key] = by_name.get(key, 0.0) + (b - a) / len(devs)
    first = trace["devices"][devs[0]]
    gaps = subtract([(lo, hi)],
                    union(clip([(a, b) for _, a, b in first], lo, hi)))
    named: Dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) / 2
        cover = [s for s in trace["spans"]
                 if s[0] != "window" and s[1] <= mid < s[2]]
        name = max(cover, key=lambda s: s[1])[0] if cover else "no_span"
        named[name] = named.get(name, 0.0) + (b - a)
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa
    return {"window_s": hi - lo,
            "busy_s": sum(busy_s) / len(busy_s),
            "device_ops": [[k, v] for k, v in rank(by_name)],
            "idle_gaps": [[k, v] for k, v in rank(named)],
            "by_name": by_name, "chips": len(devs)}


def time_matching(reduced: dict, pattern: str) -> float:
    """Device seconds (mean over chips) of operations whose base name
    matches `pattern`."""
    rx = re.compile(pattern)
    return sum(v for k, v in reduced.get("by_name", {}).items()
               if rx.search(k))


def newest_xplane(trace_dir: str) -> Optional[str]:
    import glob
    import os
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def dump(path: str) -> None:
    """What is in the file, for reading one by hand."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            ev = list(line.events)
            print(f"  LINE {line.name!r}: {len(ev)} events")
            for e in ev[:6]:
                print(f"      {e.name[:90]!r} start={e.start_ns} "
                      f"dur={e.duration_ns}")
    trace = load(path)
    red = reduce(trace, top=15)
    red.pop("by_name", None)
    print(red)
    first = {}
    for ev in trace["devices"].values():
        for n, _, _ in ev:
            first.setdefault(base_name(n), n)
    for name, _ in red.get("device_ops", []):
        print(f"EXAMPLE {name}: {first.get(name, '')[:700]}")


if __name__ == "__main__":
    dump(sys.argv[1])
