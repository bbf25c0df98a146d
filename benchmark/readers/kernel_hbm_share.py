from lib import peaks, program_trace


def read(run, root, span, arg, phases, scale=1.0):
    """A kernel's share of the chip's peak memory bandwidth: the bytes
    the program counted for it (argument `arg` of its phase spans
    `span`) per device second under the scopes matching `phases`
    (program_trace.counted_rate), over the peak."""
    t = program_trace.of_run(run)
    if t is None or not t["scoped"].get("program") \
            or not run.get("device_kind"):
        return None
    rate = program_trace.counted_rate(t, root, span, arg, phases)
    if rate is None:
        return None
    return scale * rate / peaks.peak_for(
        run["device_kind"])["hbm_bytes_per_s"]
