"""What the PROGRAM says about a profiler trace: device time by the
program's named scopes, and device-idle time by the program's own phase
spans, on the trace's clock.

    python3 benchmark/lib/program_trace.py <trace.xplane.pb>   # the table

lib/trace_reduce.py reads a trace through `jax.profiler.ProfileData`,
which shows an event's name, start and duration and nothing else. The
`.xplane.pb` holds more, on each device operation's event METADATA:
`tf_op` (JAX's name stack, e.g. `jit(_mixed_impl)/serve_step/layer3/
attn/...` — where a `jax.named_scope` of the program lands),
`hlo_category`, `flops`, `bytes_accessed`, `source`; and on each host
span the keyword arguments its `TraceAnnotation` was given. So this
module decodes the file's protobuf messages itself (XSpace, XPlane,
XLine, XEvent, XStat, XEventMetadata, XStatMetadata; field numbers from
tsl/profiler/protobuf/xplane.proto) with a small wire decoder: no new
dependency, and no TensorFlow in the benchmark's process. The interval
arithmetic is trace_reduce's, by import.

scope path  the name stack without what JAX adds: `jit(...)` elements
            and the primitive's name go, transform wrappers are peeled
            (`transpose(jvp(layer0_attn))` -> `layer0_attn`, backward).
            Forward operations of a differentiated function read
            `jvp(<scope>)`, backward ones `transpose(jvp(<scope>))`.
phase       the first element of the scope path after the program's
            root scope and its layer (`layer3/attn`, or the executor's
            `layer3_attn`: both phase `attn` of `layer3`); an operation
            with an empty scope path is UNSCOPED (reported by name)
phase span  a host span the program wrote through `Telemetry.timed`
            (`ff:<name>`), with its arguments
host gap    device-idle time inside a root phase span (`ff:serve_step`,
            `ff:train_step`), divided among the innermost child spans
"""

from __future__ import annotations

import os
import re
import struct
import sys
from typing import Dict, Iterator, List, NamedTuple, Optional, Tuple

try:
    from . import trace_reduce as tr
except ImportError:                 # run as a script, from any directory
    import trace_reduce as tr

PHASE_PREFIX = "ff:"
ROOT_SCOPES = ("serve_step",)        # stripped from the front of a path
# scopes only the program's own code writes: a trace that holds neither
# comes from a program without scopes (JAX itself names some operations
# — an einsum by its formula, a Pallas call by its kernel — so "some
# scope" is not the test)
PROGRAM_SCOPES = ("serve_step", "optimizer")
LAYER = re.compile(r"^(layer\d+)(?:_(\w+))?$")


# ------------------------------------------------------------ wire format
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    shift = val = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message: varints and
    fixed-width values as unsigned ints, length-delimited as bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 1:
            val, i = struct.unpack_from("<Q", buf, i)[0], i + 8
        elif wt == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wt == 5:
            val, i = struct.unpack_from("<I", buf, i)[0], i + 4
        else:
            raise ValueError(f"xplane: wire type {wt} at byte {i}")
        yield num, wt, val


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf: bytes, stat_names: Dict[int, str]) -> Tuple[str, object]:
    """XStat -> (name, value). A `ref_value` names another stat
    metadata entry whose name is the string meant."""
    mid, val = 0, None
    for num, _, v in _fields(buf):
        if num == 1:
            mid = v
        elif num == 2:
            val = struct.unpack("<d", struct.pack("<Q", v))[0]
        elif num == 3:
            val = v
        elif num == 4:
            val = _signed(v)
        elif num in (5, 6):
            val = bytes(v).decode("utf-8", "replace")
        elif num == 7:
            val = stat_names.get(v, "")
    return stat_names.get(mid, str(mid)), val


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, val = 0, b""
    for num, _, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def decode(path: str) -> List[dict]:
    """The file's planes: [{name, lines: [{name, events: [{name,
    start_ps, duration_ps, stats, meta}]}]}], `start_ps` from
    the start of the trace, `stats` the event's own, `meta` its
    metadata's (shared by every event of one operation)."""
    with open(path, "rb") as f:
        space = f.read()
    planes = []
    for num, _, pbuf in _fields(space):
        if num != 1:
            continue
        name, lines, ev_meta, stat_names = "", [], {}, {}
        for pn, _, v in _fields(pbuf):
            if pn == 2:
                name = bytes(v).decode()
            elif pn == 3:
                lines.append(v)
            elif pn == 4:
                k, m = _map_entry(v)
                ev_meta[k] = m
            elif pn == 5:
                k, m = _map_entry(v)
                stat_names[k] = next(
                    (bytes(x).decode() for n, _, x in _fields(m)
                     if n == 2), "")
        metas = {}
        for k, m in ev_meta.items():
            mname, mstats = "", {}
            for n, _, x in _fields(m):
                if n == 2:
                    mname = bytes(x).decode("utf-8", "replace")
                elif n == 5:
                    sk, sv = _stat(x, stat_names)
                    mstats[sk] = sv
            metas[k] = (mname, mstats)
        out_lines = []
        for lbuf in lines:
            lname, t0_ns, events = "", 0, []
            for ln, _, v in _fields(lbuf):
                if ln == 2:
                    lname = bytes(v).decode()
                elif ln == 3:
                    t0_ns = _signed(v)
                elif ln == 4:
                    events.append(v)
            evs = []
            for ebuf in events:
                mid = off = dur = 0
                stats = {}
                for en, _, v in _fields(ebuf):
                    if en == 1:
                        mid = v
                    elif en == 2:
                        off = _signed(v)
                    elif en == 3:
                        dur = _signed(v)
                    elif en == 4:
                        sk, sv = _stat(v, stat_names)
                        stats[sk] = sv
                mname, mstats = metas.get(mid, ("", {}))
                evs.append({"name": mname,
                            "start_ps": t0_ns * 1000 + off,
                            "duration_ps": dur, "stats": stats,
                            "meta": mstats})
            out_lines.append({"name": lname, "events": evs})
        planes.append({"name": name, "lines": out_lines})
    return planes


# ------------------------------------------------------------ the program
class Op(NamedTuple):
    """One executed device operation."""
    name: str           # the HLO instruction, as trace_reduce sees it
    start: float        # seconds on the trace's clock
    end: float
    tf_op: str          # JAX's name stack, "" where XLA kept none
    flops: float        # XLA's own counts for one execution
    bytes_accessed: float


class Span(NamedTuple):
    name: str           # without its prefix
    start: float
    end: float
    args: dict


# `tf_op` is `<name stack>:<type>`; a fusion of several operations may
# carry several, joined by `;` — the first is the one read
_TF_OP_REST = re.compile(r"(:\w*)?(;.*)?$")


def load(path: str) -> dict:
    """{'devices': {chip: [Op]}, 'phases': [Span] (the program's `ff:`
    spans), 'bench': [Span] (the benchmark's `bench:` spans)}, sorted
    by start, seconds on the trace's clock (the one trace_reduce.load
    uses)."""
    devices: Dict[int, List[Op]] = {}
    phases: List[Span] = []
    bench: List[Span] = []
    for plane in decode(path):
        m = tr.DEVICE_PLANE.match(plane["name"])
        if m:
            for line in plane["lines"]:
                if line["name"] != tr.OPS_LINE:
                    continue
                devices[int(m.group(1))] = [
                    Op(e["name"], e["start_ps"] * 1e-12,
                       (e["start_ps"] + e["duration_ps"]) * 1e-12,
                       _TF_OP_REST.sub("", str(e["meta"].get("tf_op")
                                               or "")),
                       float(e["meta"].get("flops") or 0.0),
                       float(e["meta"].get("bytes_accessed") or 0.0))
                    for e in line["events"]]
        elif plane["name"] == "/host:CPU":
            for line in plane["lines"]:
                for e in line["events"]:
                    for prefix, into in ((PHASE_PREFIX, phases),
                                         (tr.SPAN_PREFIX, bench)):
                        if e["name"].startswith(prefix):
                            into.append(Span(
                                e["name"][len(prefix):],
                                e["start_ps"] * 1e-12,
                                (e["start_ps"] + e["duration_ps"])
                                * 1e-12, dict(e["stats"])))
    return {"devices": devices,
            "phases": sorted(phases, key=lambda s: s.start),
            "bench": sorted(bench, key=lambda s: s.start)}


def window_of(trace: dict) -> Optional[tr.Interval]:
    """The `bench:window` span, else first to last device operation."""
    for s in trace["bench"]:
        if s.name == "window":
            return (s.start, s.end)
    ops = [o for d in trace["devices"].values() for o in d]
    if not ops:
        return None
    return (min(o.start for o in ops), max(o.end for o in ops))


def _split(path: str) -> List[str]:
    """Split a name stack on the `/` that lie outside parentheses."""
    out, depth, cur = [], 0, []
    for ch in path:
        if ch == "/" and depth == 0:
            out.append("".join(cur))
            cur = []
            continue
        depth += ch == "("
        depth -= ch == ")"
        cur.append(ch)
    out.append("".join(cur))
    return out


_WRAP = re.compile(r"^(\w+)\((.*)\)$")
_JIT = ("jit", "pjit", "xla_call")


def scope_path(tf_op: str) -> Tuple[Tuple[str, ...], bool]:
    """(the program's scopes around an operation, outermost first;
    whether it is a backward operation). Empty for an operation XLA
    gave no name stack, or one that lies under no scope."""
    if not tf_op:
        return (), False
    backward = False
    scopes: List[str] = []
    tokens = _split(tf_op)[:-1]            # the last is the primitive
    while tokens:
        tok = tokens.pop(0)
        m = _WRAP.match(tok)
        if m is None:
            if tok:
                scopes.append(tok)
            continue
        if m.group(1) in _JIT:
            continue
        backward |= m.group(1) == "transpose"
        tokens[:0] = _split(m.group(2))    # peel one transform
    return tuple(scopes), backward


def phase_of(scopes: Tuple[str, ...]) -> Tuple[str, Optional[str]]:
    """(phase, layer) of a scope path: `('serve_step', 'layer3',
    'attn')` -> ('attn', 'layer3'); the executor's op names carry
    their layer in front, `('layer0_ff1',)` -> ('ff1', 'layer0');
    `('loss',)` -> ('loss', None); () -> ('', None)."""
    rest = [s for s in scopes if s not in ROOT_SCOPES]
    if not rest:
        return "", None
    m = LAYER.match(rest[0])
    if m is None:
        return rest[0], None
    if m.group(2):
        return m.group(2), m.group(1)
    return (rest[1] if len(rest) > 1 else m.group(1)), m.group(1)


_OPERAND = re.compile(r"%[\w.\-]+")


def _placed(trace: dict, devs: List[int]) -> Dict[str, tuple]:
    """instruction text -> (phase, layer, backward, attributed): where
    each distinct device operation of the trace belongs. An operation
    whose `tf_op` names no program scope (the compiler's own copies and
    slices carry no name stack) is ATTRIBUTED to the phase of the first
    operand some scoped operation produced, else of the first scoped
    operation that uses it — read off the HLO text that is the event's
    name (`%copy.4 = ... copy(... %fusion.49)`). It stays unscoped
    where neither has a scope."""
    own: Dict[str, tuple] = {}
    for d in devs:
        for op in trace["devices"][d]:
            if op.name not in own:
                scopes, backward = scope_path(op.tf_op)
                own[op.name] = phase_of(scopes) + (backward,)
    inst = {name.split(" = ")[0]: name for name in own}
    uses: Dict[str, List[str]] = {}
    for name in own:
        for operand in _OPERAND.findall(name.partition(" = ")[2]):
            uses.setdefault(operand, []).append(name)
    out = {}
    for name, (phase, layer, backward) in own.items():
        attributed = ""
        if not phase:
            near = [inst[o] for o in
                    _OPERAND.findall(name.partition(" = ")[2])
                    if o in inst] + uses.get(name.split(" = ")[0], [])
            attributed = next((own[n][0] for n in near if own[n][0]), "")
        out[name] = (phase, layer, backward, attributed)
    return out


def by_scope(trace: dict, chips: Optional[int] = None) -> dict:
    """Device seconds inside the window (mean over chips):

    program   whether any operation lies under one of PROGRAM_SCOPES
    phases    {phase: {seconds, forward_s, backward_s, flops, bytes
              (XLA's own counts, summed over the executions counted),
              ops {operation name: seconds}, attributed_s (unscoped
              operations attributed to the phase, NOT in `seconds`)}}
    layers    {(phase, layer): seconds}
    unscoped  {operation name: seconds} of every operation whose
              `tf_op` names no program scope, attributed or not
    attributed {operation name: {phase: seconds}}"""
    win = window_of(trace)
    devs = sorted(trace["devices"])[:chips]
    if win is None or not devs:
        return {}
    lo, hi = win
    placed = _placed(trace, devs)
    phases: Dict[str, dict] = {}
    layers: Dict[Tuple[str, str], float] = {}
    unscoped: Dict[str, float] = {}
    attributed: Dict[str, Dict[str, float]] = {}

    def row(phase):
        return phases.setdefault(phase, {
            "seconds": 0.0, "forward_s": 0.0, "backward_s": 0.0,
            "flops": 0.0, "bytes": 0.0, "ops": {}, "attributed_s": 0.0})

    for d in devs:
        for op in trace["devices"][d]:
            a, b = max(op.start, lo), min(op.end, hi)
            if b <= a:
                continue
            secs = (b - a) / len(devs)
            share = (b - a) / (op.end - op.start)   # clipped at an edge
            phase, layer, backward, near = placed[op.name]
            key = tr.base_name(op.name)
            if not phase:
                unscoped[key] = unscoped.get(key, 0.0) + secs
                if near:
                    row(near)["attributed_s"] += secs
                    into = attributed.setdefault(key, {})
                    into[near] = into.get(near, 0.0) + secs
                continue
            r = row(phase)
            r["seconds"] += secs
            r["backward_s" if backward else "forward_s"] += secs
            r["flops"] += op.flops * share / len(devs)
            r["bytes"] += op.bytes_accessed * share / len(devs)
            r["ops"][key] = r["ops"].get(key, 0.0) + secs
            if layer:
                layers[(phase, layer)] = layers.get((phase, layer),
                                                    0.0) + secs
    program = any(s in PROGRAM_SCOPES for tf_op in
                  {op.tf_op for d in devs for op in trace["devices"][d]}
                  for s in scope_path(tf_op)[0])
    return {"window_s": hi - lo, "phases": phases, "layers": layers,
            "program": program,
            "unscoped": unscoped, "attributed": attributed,
            "unscoped_s": sum(unscoped.values()),
            "scoped_s": sum(r["seconds"] for r in phases.values())}


def seconds_matching(scoped: dict, pattern: str,
                     with_attributed: bool = False) -> float:
    """Device seconds of the phases whose name matches `pattern`, with
    or without the unscoped operations attributed to them."""
    rx = re.compile(pattern)
    return sum(r["seconds"] + (r["attributed_s"] if with_attributed else 0)
               for name, r in scoped.get("phases", {}).items()
               if rx.search(name))


def whole_steps(trace: dict, root: str) -> List[Span]:
    """The program's root phase spans named `root` that lie wholly
    inside the window. A step's span ends after its results were
    fetched, so it holds all of the step's device time."""
    win = window_of(trace)
    if win is None:
        return []
    return [s for s in trace["phases"]
            if s.name == root and win[0] <= s.start and s.end <= win[1]]


def host_gaps(trace: dict, root: str) -> dict:
    """Device-idle time (chip 0) inside the program's root phase spans
    named `root`:

    idle_s    the idle seconds of each span wholly inside the window
    by_phase  the idle seconds of every such span that meets the
              window (clipped to it) by innermost child phase span,
              `<root>` itself for what no child covers: a gap is cut
              at the spans' edges, so every idle second has one owner
    total_s   their sum
    whole_gaps_s  the length of the window's idle gaps whose MIDDLE a
              root span covers, each gap whole: trace_reduce's rule for
              naming a gap, so this is what its `idle_gaps` puts under
              the benchmark span around the root span; the excess over
              `total_s` lies outside the program's span"""
    win = window_of(trace)
    devs = sorted(trace["devices"])
    if win is None or not devs:
        return {}
    lo, hi = win
    busy = tr.union([(o.start, o.end) for o in trace["devices"][devs[0]]])
    roots = [r for r in trace["phases"]
             if r.name == root and r.end > lo and r.start < hi]
    per_step: List[float] = []
    by_phase: Dict[str, float] = {}
    for r in roots:
        a, b = max(r.start, lo), min(r.end, hi)
        idle = tr.subtract([(a, b)], tr.clip(busy, a, b))
        if (a, b) == (r.start, r.end):
            per_step.append(tr.total(idle))
        kids = [s for s in trace["phases"] if s.name != root
                and r.start <= s.start and s.end <= r.end]
        owned: List[tr.Interval] = []
        # innermost first: a later-starting span lies inside an earlier
        for k in sorted(kids, key=lambda s: -s.start):
            mine = tr.subtract(tr.clip(idle, k.start, k.end),
                               tr.union(owned))
            by_phase[k.name] = by_phase.get(k.name, 0.0) + tr.total(mine)
            owned += mine
        rest = tr.subtract(idle, tr.union(owned))
        by_phase[root] = by_phase.get(root, 0.0) + tr.total(rest)
    whole = sum(b - a for a, b in tr.subtract([(lo, hi)],
                                              tr.clip(busy, lo, hi))
                if any(r.start <= (a + b) / 2 < r.end for r in roots))
    return {"idle_s": per_step, "by_phase": by_phase,
            "total_s": sum(by_phase.values()), "whole_gaps_s": whole}


def seconds_in(trace: dict, pattern: str,
               spans: List[Span]) -> float:
    """Device seconds (chip 0) of the operations whose phase matches
    `pattern`, inside the given host spans."""
    rx = re.compile(pattern)
    devs = sorted(trace["devices"])
    iv = tr.union([(s.start, s.end) for s in spans])
    if not devs or not iv:
        return 0.0
    match: Dict[str, bool] = {}
    secs = 0.0
    for op in trace["devices"][devs[0]]:
        if op.end <= iv[0][0] or op.start >= iv[-1][1]:
            continue
        if op.tf_op not in match:
            match[op.tf_op] = bool(rx.search(
                phase_of(scope_path(op.tf_op)[0])[0]))
        if match[op.tf_op]:
            secs += tr.total(tr.clip(iv, op.start, op.end))
    return secs


def counted_rate(trace: dict, root: str, span: str, arg: str,
                 phases: str) -> Optional[float]:
    """What the program counted per device second: the sum of argument
    `arg` of its phase spans `span`, over the device seconds under the
    scopes matching `phases` — both over the root spans `root` that lie
    wholly in the window, each of which holds one step's count and all
    of that step's device time. None where there is nothing to divide."""
    steps = whole_steps(trace, root)
    counts = [float(s.args[arg]) for s in trace["phases"]
              if s.name == span and arg in s.args
              and any(r.start <= s.start and s.end <= r.end
                      for r in steps)]
    secs = seconds_in(trace, phases, steps)
    return sum(counts) / secs if counts and secs else None


ROOT_SPANS = ("serve_step", "train_step")
_CACHE: Dict[str, dict] = {}


def _top(d: Dict[str, float], n: int = 12) -> Dict[str, float]:
    return dict(sorted(d.items(), key=lambda kv: -kv[1])[:n])


def of_run(run: dict) -> Optional[dict]:
    """The loaded trace of a benchmark run (`run["trace"]["trace_file"]`)
    with its reductions (`scoped`, `gaps` by root span), or None where
    the run was not traced. Kept per file, so a run's readers decode it
    once; the first to ask also prints what no metric holds, as a
    progress line: device seconds by phase and, inside a phase, by
    operation; the unscoped operations by name and the phase each was
    attributed to; idle seconds by phase span beside the benchmark's
    own gaps."""
    path = (run.get("trace") or {}).get("trace_file")
    if not path or not os.path.exists(path):
        return None
    if path not in _CACHE:
        trace = load(path)
        sc = trace["scoped"] = by_scope(
            trace, chips=run["trace"].get("chips"))
        names = {s.name for s in trace["phases"]}
        trace["gaps"] = {r: host_gaps(trace, r) for r in ROOT_SPANS
                         if r in names}
        _CACHE[path] = trace
        if sc:
            import json
            print("# program_trace: " + json.dumps({
                "window_s": sc["window_s"],
                "phase_s": _top({k: v["seconds"]
                                 for k, v in sc["phases"].items()}),
                "phase_ops_s": {k: _top(v["ops"], 4)
                                for k, v in sc["phases"].items()
                                if len(v["ops"]) > 1},
                "unscoped_s": _top(sc["unscoped"]),
                "attributed_s": sc["attributed"],
                "idle_by_phase_span_s": {
                    r: {**g["by_phase"], "_total": g["total_s"],
                        "_whole_gaps": g["whole_gaps_s"]}
                    for r, g in trace["gaps"].items()},
                "bench_idle_gaps_s": run["trace"].get("idle_gaps")}),
                flush=True)
    return _CACHE[path]


# ------------------------------------------------------------ the table
def table(path: str) -> str:
    trace = load(path)
    sc = by_scope(trace)
    if not sc:
        return "no device plane in " + path
    win = sc["window_s"]
    out = [f"window {win:.6f} s; device time under a scope "
           f"{sc['scoped_s']:.6f} s, under none {sc['unscoped_s']:.6f} s",
           "", f"{'phase':<12}{'seconds':>10}{'% window':>9}"
           f"{'fwd s':>10}{'bwd s':>10}{'GB/s':>8}{'TFLOP/s':>8}"
           f"{'+attributed':>12}  operations"]
    for name, r in sorted(sc["phases"].items(),
                          key=lambda kv: -kv[1]["seconds"]):
        s = r["seconds"]
        ops = ", ".join(f"{k} {v:.4f}" for k, v in _top(r["ops"], 3).items())
        out.append(f"{name:<12}{s:>10.6f}{100 * s / win:>9.2f}"
                   f"{r['forward_s']:>10.6f}{r['backward_s']:>10.6f}"
                   f"{r['bytes'] / s / 1e9:>8.1f}"
                   f"{r['flops'] / s / 1e12:>8.2f}"
                   f"{r['attributed_s']:>12.6f}  {ops}")
    out += ["", "operations under no scope (and the phase of the "
            "operation that feeds or uses them):"]
    for name, s in sorted(sc["unscoped"].items(), key=lambda kv: -kv[1]):
        near = ", ".join(f"{k} {v:.6f}" for k, v in
                         sc["attributed"].get(name, {}).items())
        out.append(f"  {name:<32}{s:>10.6f}{100 * s / win:>7.2f} %  "
                   f"{near}")
    lay: Dict[str, Dict[str, float]] = {}
    for (phase, layer), s in sc["layers"].items():
        lay.setdefault(layer, {})[phase] = s
    if lay:
        cols = sorted({p for v in lay.values() for p in v})
        out += ["", "seconds by layer x phase:",
                f"{'layer':<10}" + "".join(f"{c:>11}" for c in cols)]
        for layer in sorted(lay, key=lambda n: int(n[5:])):
            out.append(f"{layer:<10}" + "".join(
                f"{lay[layer].get(c, 0.0):>11.6f}" for c in cols))
    for root in sorted({s.name for s in trace["phases"]}
                       & set(ROOT_SPANS)):
        g = host_gaps(trace, root)
        out += ["", f"device idle inside ff:{root} "
                f"({len(g['idle_s'])} whole spans, {g['total_s']:.6f} s "
                f"in the window; the gaps it covers the middle of, "
                f"whole: {g['whole_gaps_s']:.6f} s) by phase span:"]
        for name, s in sorted(g["by_phase"].items(), key=lambda kv: -kv[1]):
            out.append(f"  {name:<20}{s:>10.6f}")
    return "\n".join(out)


if __name__ == "__main__":
    print(table(sys.argv[1]))
