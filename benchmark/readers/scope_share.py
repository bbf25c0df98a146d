from lib import program_trace


def read(run, phases=None, with_attributed=False, scale=1.0):
    """A share of the traced window: device time of the operations
    that lie under the program's named scopes whose phase matches
    `phases` (`with_attributed`: and of the compiler's unscoped
    operations attributed to those phases), or, with no pattern, under
    no scope at all. Nothing to read where the run was not traced or
    the program wrote no scope."""
    t = program_trace.of_run(run)
    if t is None or not t["scoped"].get("program"):
        return None
    sc = t["scoped"]
    secs = sc["unscoped_s"] if phases is None \
        else program_trace.seconds_matching(sc, phases, with_attributed)
    return scale * secs / sc["window_s"]
