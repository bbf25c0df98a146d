from lib import program_trace


def step_args(t, root, span, keys):
    """The arguments of the program's phase spans `span` that carry
    every one of `keys`, over the root spans `root` that lie wholly in
    the traced window: one dict a step."""
    steps = program_trace.whole_steps(t, root)
    return [s.args for s in t["phases"]
            if s.name == span and all(k in s.args for k in keys)
            and any(r.start <= s.start and s.end <= r.end for r in steps)]


def of_trace(t, root, span, num, den):
    args = step_args(t, root, span, (num, den))
    total = sum(float(a[den]) for a in args)
    return sum(float(a[num]) for a in args) / total if total else None


def read(run, root, span, num, den, scale=1.0):
    """One count of the program over another, both made where the work
    is (arguments `num` and `den` of its phase spans `span`): the sum
    of the one over the sum of the other, over the steps wholly in the
    traced window. Nothing to read where the run was not traced or the
    program wrote no such arguments."""
    t = program_trace.of_run(run)
    v = None if t is None else of_trace(t, root, span, num, den)
    return None if v is None else scale * v
