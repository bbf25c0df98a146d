def read(run, counts):
    """The sum of some counts of the window over the window's seconds:
    all the work and all the time."""
    n = run["numbers"]
    if any(n.get(c) is None for c in counts) or not n.get("seconds"):
        return None
    return sum(n[c] for c in counts) / n["seconds"]
