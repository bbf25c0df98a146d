from lib import trace_reduce


def read(run, pattern=None, key=None, scale=1.0):
    """A share of the traced window: device time of the operations
    whose name matches `pattern`, or a reduced number named by `key`."""
    t = run["trace"]
    if not t or not t.get("window_s"):
        return None
    secs = trace_reduce.time_matching(t, pattern) if pattern else t.get(key)
    return None if secs is None else scale * secs / t["window_s"]
