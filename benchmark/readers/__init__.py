"""One small reader per kind of metric: `read(run, **args)` takes the
number from the run's counters (`run["numbers"]`) or its reduced trace
(`run["trace"]`), and returns None where there is nothing to read."""
