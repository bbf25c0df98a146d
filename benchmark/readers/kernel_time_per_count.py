import re

from lib import program_trace
from lib import trace_reduce as tr
from readers.span_ratio import step_args


def of_trace(t, root, span, arg, ops):
    count = sum(float(a[arg]) for a in step_args(t, root, span, (arg,)))
    devs = sorted(t["devices"])
    iv = tr.union([(s.start, s.end)
                   for s in program_trace.whole_steps(t, root)])
    if not count or not devs or not iv:
        return None
    rx = re.compile(ops)
    secs = sum(tr.total(tr.clip(iv, op.start, op.end))
               for op in t["devices"][devs[0]]
               if op.end > iv[0][0] and op.start < iv[-1][1]
               and rx.search(op.name))
    return secs / count if secs else None


def read(run, root, span, arg, ops, scale=1.0):
    """A kernel's device seconds for each unit of what the program
    counted for it: the seconds (chip 0) of the operations whose NAME
    matches `ops`, over the sum of argument `arg` of the program's
    phase spans `span`, both over the root spans `root` that lie wholly
    in the traced window. Nothing to read where the run was not traced,
    the program wrote no such argument, or no such operation ran."""
    t = program_trace.of_run(run)
    v = None if t is None else of_trace(t, root, span, arg, ops)
    return None if v is None else scale * v
