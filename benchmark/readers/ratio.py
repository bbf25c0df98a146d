def read(run, num, den, scale=1.0):
    n = run["numbers"]
    if n.get(num) is None or not n.get(den):
        return None
    return scale * n[num] / n[den]
