def read(run, series, scale=1.0):
    v = run["numbers"].get(series)
    return sum(v) / len(v) * scale if v else None
