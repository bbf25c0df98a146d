from lib import peaks, program_trace
from lib import trace_reduce as tr


def read(run, root, span, arg, ops, scale=1.0):
    """A kernel's share of the chip's peak memory bandwidth, for a
    kernel the compiler NAMES itself and leaves under no scope of the
    program (XLA's grouped matmul, `ragged-dot-none`, keeps no name
    stack): the bytes the program counted for it (argument `arg` of its
    phase spans `span`) over the device seconds (chip 0) of the
    operations whose name matches `ops`, both over the root spans
    `root` that lie wholly in the traced window; over the peak.
    Nothing to read where the run was not traced, the program wrote no
    such span argument, or no such operation ran."""
    import re
    t = program_trace.of_run(run)
    if t is None or not run.get("device_kind"):
        return None
    steps = program_trace.whole_steps(t, root)
    counts = [float(s.args[arg]) for s in t["phases"]
              if s.name == span and arg in s.args
              and any(r.start <= s.start and s.end <= r.end for r in steps)]
    devs = sorted(t["devices"])
    iv = tr.union([(s.start, s.end) for s in steps])
    if not counts or not devs or not iv:
        return None
    rx = re.compile(ops)
    secs = sum(tr.total(tr.clip(iv, op.start, op.end))
               for op in t["devices"][devs[0]]
               if op.end > iv[0][0] and op.start < iv[-1][1]
               and rx.search(op.name))
    if not secs:
        return None
    return scale * sum(counts) / secs / peaks.peak_for(
        run["device_kind"])["hbm_bytes_per_s"]
