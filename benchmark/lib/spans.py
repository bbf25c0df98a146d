"""The benchmark's own spans: name, start, end on `time.perf_counter`,
kept in memory. While a profiler trace is being taken each span is also
written into the trace (`bench:<name>`), so that the reduction can name
what the host was doing during a device idle gap on the trace's clock."""

from __future__ import annotations

import contextlib
import time
from typing import List, Tuple

PREFIX = "bench:"


class Spans:
    def __init__(self):
        self.rows: List[Tuple[str, float, float]] = []
        self.annotate = False       # set while the profiler runs

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(PREFIX + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.rows.append((name, t0, time.perf_counter()))
            if ann is not None:
                ann.__exit__(None, None, None)
