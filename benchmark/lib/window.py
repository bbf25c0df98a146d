"""The measured window's bookkeeping: when set-up ended, the program's
compile counters at both ends, and — in a traced run — the profiler
over the window's last `trace_s` seconds (started inside the window,
stopped only after the run has drained, so that stopping it stalls
nothing that is measured)."""

from __future__ import annotations

import os
import shutil
import time
from typing import Callable, Optional

from . import trace_reduce
from .spans import Spans


class Window:
    def __init__(self, spans: Spans, compile_counts: Callable[[], dict],
                 trace_dir: Optional[str], trace_s: float):
        self.spans = spans
        self.compile_counts = compile_counts
        self.trace_dir = trace_dir      # None = not a traced run
        self.trace_s = trace_s
        self.t_open = None
        self.counts_open = None
        self.counts_close = None
        self._tracing = False
        self._ann = None

    def tick(self, now: float, w0: float, w1: float) -> None:
        """Called once per turn of a driver's loop."""
        if self.t_open is None and now >= w0:
            self.t_open = now
            self.counts_open = dict(self.compile_counts())
        if self.trace_dir and not self._tracing \
                and max(w0, w1 - self.trace_s) <= now < w1:
            self._start()
        if self._ann is not None and now >= w1:
            self._close_window_span()   # the trace itself stays on
        if self.counts_close is None and now >= w1:
            self.counts_close = dict(self.compile_counts())

    def _start(self) -> None:
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        os.makedirs(self.trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # our spans only, not every call
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._tracing = True
        self.spans.annotate = True
        self._ann = jax.profiler.TraceAnnotation("bench:window")
        self._ann.__enter__()

    def _close_window_span(self) -> None:
        self._ann.__exit__(None, None, None)
        self._ann = None

    def finish(self, chips: int) -> dict:
        """Stop the profiler (if it ran) and reduce its trace."""
        if self.counts_close is None:
            self.counts_close = dict(self.compile_counts())
        if not self._tracing:
            return {}
        import jax
        if self._ann is not None:
            self._close_window_span()
        self.spans.annotate = False
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self._tracing = False
        path = trace_reduce.newest_xplane(self.trace_dir)
        if path is None:
            return {}
        reduced = trace_reduce.reduce(trace_reduce.load(path), chips=chips)
        if not reduced:             # no device plane: nothing to report
            return {}
        reduced["trace_file"] = path
        reduced["trace_stop_and_reduce_s"] = time.perf_counter() - t0
        return reduced

    def compiles_in_window(self) -> Optional[int]:
        if self.counts_open is None or self.counts_close is None:
            return None
        return sum(self.counts_close.values()) - sum(self.counts_open.values())
