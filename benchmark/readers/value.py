def read(run, key, scale=1.0):
    """A number the run counted, as it is."""
    v = run["numbers"].get(key)
    return None if v is None else v * scale
