from lib import peaks


def read(run, scale=1.0):
    """Model FLOP/s utilisation: operations forward and backward need
    per token (lib/flops.py, no recomputation) x tokens per second,
    over chips x the chip's published bf16 peak."""
    n = run["numbers"]
    if not n.get("train_tokens") or not n.get("flops_per_token") \
            or not run.get("device_kind"):
        return None
    peak = peaks.peak_for(run["device_kind"])["bf16_flops"]
    rate = n["train_tokens"] / n["seconds"]
    return scale * n["flops_per_token"] * rate / (n["chips"] * peak)
