import re

from lib import program_trace


def of_trace(t, root, phase):
    by_phase = (t["gaps"].get(root) or {}).get("by_phase", {})
    under = [s for name, s in by_phase.items() if re.fullmatch(phase, name)]
    win = program_trace.window_of(t)
    if not under or win is None:
        return None
    steps = sum(1 for r in t["phases"]
                if r.name == root and r.end > win[0] and r.start < win[1])
    return sum(under) / steps


def read(run, root, phase, scale=1.0):
    """Device-idle seconds a step that lie under the program's phase
    spans whose whole name matches `phase`, innermost:
    program_trace.host_gaps cuts the idle inside every root span `root`
    that meets the traced window at its child spans' edges
    (`by_phase`), and this divides those children's part by the number
    of those root spans. A mean, where `host_gap` reads the whole
    span's median. Two spans that share one gap (the launch and the
    wait for its result) are steadier read together, `a|b`. Nothing to
    read where the run was not traced or the program wrote no such
    span."""
    t = program_trace.of_run(run)
    v = None if t is None else of_trace(t, root, phase)
    return None if v is None else scale * v
