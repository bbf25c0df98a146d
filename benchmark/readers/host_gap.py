import statistics

from lib import program_trace


def read(run, root, scale=1.0):
    """Device-idle seconds inside the program's root phase span `root`
    (`ff:serve_step`, `ff:train_step`), median over the spans that lie
    wholly in the traced window. Nothing to read where the program
    wrote no such span."""
    t = program_trace.of_run(run)
    if t is None or not t["gaps"].get(root, {}).get("idle_s"):
        return None
    return scale * statistics.median(t["gaps"][root]["idle_s"])
