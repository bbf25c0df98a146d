"""Which state the chip, as this process reaches it, was in around a
run's window.

One TPU v5e machine runs the same compiled programs at one of two
speeds, and a run could not tell from its own step times which one its
window fell in. What E1-E3 of PR 42 found (PERF.md section 6; the
readings are testdata/chip_state_pr42.json): the state is the
PROCESS's; it is not there when JAX starts (no process of 34 began
slow), it is there when set-up ends, in about one process of three, and
it then holds to the process's end; time, load and heat do not move it.
It is not the core's clock: the probe's matmul chain reads 0.6 % slower,
its memory-bound sweep 1.2 %, `phi4flash-reason`'s step 4.8 % and
`olmoe-chat`'s 10 %. The probe here sees it: ONE set of programs, fixed
for ever, that the cells' own work does not change.

  matmul chain  MATMUL_CHAIN bf16 products of MATMUL_N^3 in a row
                (compute-bound: 0.26 s), timed on the host clock to
                `block_until_ready`, least of REPEATS
  sweep         SWEEPS elementwise passes over SWEEP_BYTES of float32
                (memory-bound: every pass reads and writes the array;
                0.13 s), least of two
  round trip    one tiny program to `block_until_ready`, the median of
                ROUND_TRIPS: what a synchronisation costs, alone

Operations and bytes are counted here from the shapes (`matmul_flops`,
`sweep_traffic_bytes`). The two long
programs take their trip count as an argument, so one executable
serves the short untimed call that loads it and the timed one, and the
compiler cannot unroll and fuse the passes. The probe holds under
0.6 GB while it runs (three 128 MiB matrices and the chain's second
product, then one 256 MiB array updated in place) and frees all of it.

A run takes the probe at three points — after JAX starts, after set-up
before the ramp, after the drain — and prints `# chip_state: {...}`.
A reading is "slow" against the process's OWN first reading (which no
process of E1-E3 had slow), so a chip that is a little faster or slower
than the one measured needs no other number. The gate: a process whose
reading before the ramp is slow gives its window up and exits with
EXIT_SLOW, and run.py starts another — while the starts given up have
cost no more than GATE_BUDGET_S together, and never in a traced run
(it reports its state beside its shares: `chip_probe_tflops.*`). `state` is "fast", "slow" or
"mixed" by the two readings around the window and by the median step
time of each third of the window.
"""

from __future__ import annotations

import functools
import statistics
import time
from typing import List, Optional, Sequence

MATMUL_N = 8192
MATMUL_CHAIN = 44       # 0.26 s at 185 TFLOP/s: over the 250 ms a host-
#                         clock reading has to span (40 would be 0.238)
SWEEP_BYTES = 256 * 2 ** 20
SWEEPS = 160            # 86 GB moved: 0.13 s at 650 GB/s (a control, not
#                         a metric: half a millisecond of the clock is 0.4 %)
REPEATS = 3
ROUND_TRIPS = 64
POINTS = ("start", "before_ramp", "after_drain")

# The line between the two states: a reading over the process's first.
# Source: E3 of PR 42 (my chip runs; testdata/chip_state_pr42.json): in
# the 12 runs whose steps read fast, the chain's rate before the ramp and
# after the drain was 0.9982-0.9999 of the first reading and the sweep's
# 0.9979-1.0025; in the 3 whose steps read 4.8-10 % slow 0.9929-0.9955
# and 0.9851-0.9913. Either reading under its line says slow: a reading
# disturbed on the host can only read low, and a start given up for it
# costs seconds, where a slow window taken for a fast one costs a verdict.
SLOW_UNDER = {"tflops": 0.9965, "gbps": 0.994}
# Thirds of one window whose median step times differ by more than this
# share of the least are two states in one run: inside one state they
# differ by up to 1.5 % (the load moves), the states by 4.8 % and more.
THIRDS_APART = 0.03
# What the starts a run gives up may cost together, in seconds (a start
# is given up at the end of its set-up, 19-27 s warm: two or three).
GATE_BUDGET_S = 60.0
EXIT_SLOW = 75          # "try again": sysexits' EX_TEMPFAIL


class SlowChip(Exception):
    """Raised out of the cell's code by a process that gives its window
    up; run.py turns it into EXIT_SLOW."""


def matmul_flops(n: int = MATMUL_N, chain: int = MATMUL_CHAIN) -> float:
    """One multiply-add is 2 operations: 2 n^3 a product."""
    return 2.0 * n ** 3 * chain


def sweep_traffic_bytes(nbytes: int = SWEEP_BYTES,
                        sweeps: int = SWEEPS) -> float:
    """Every pass reads the array once and writes it once."""
    return 2.0 * nbytes * sweeps


def held_bytes(n: int = MATMUL_N, nbytes: int = SWEEP_BYTES) -> int:
    """The most the probe holds at once: both operands and the product
    (bf16), or the swept array."""
    return max(3 * n * n * 2, nbytes)


@functools.lru_cache(maxsize=None)
def _programs():
    """The probe's jitted programs, made once a process (JAX is
    imported only when a probe is taken)."""
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnames=("n",))
    def operands(n):
        ka, kb = jax.random.split(jax.random.PRNGKey(0))
        a = jax.random.normal(ka, (n, n), jnp.bfloat16)
        # rows of about unit length: the chain neither grows nor dies
        b = jax.random.normal(kb, (n, n), jnp.float32) / (n ** 0.5)
        return a, b.astype(jnp.bfloat16)

    @jax.jit
    def chain(a, b, steps):
        return jax.lax.fori_loop(0, steps, lambda _, x: jnp.dot(x, b), a)

    @functools.partial(jax.jit, static_argnames=("count",))
    def array(count):
        return jnp.ones((count,), jnp.float32)

    @functools.partial(jax.jit, donate_argnums=0)
    def sweep(x, steps):
        return jax.lax.fori_loop(
            0, steps, lambda _, v: v * 1.0001 + 0.001, x)

    @jax.jit
    def tiny(v):
        return v + 1

    return operands, chain, array, sweep, tiny


def _timed(fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    out.block_until_ready()
    return time.perf_counter() - t0, out


def probe(n: int = MATMUL_N, chain_len: int = MATMUL_CHAIN,
          nbytes: int = SWEEP_BYTES, sweeps: int = SWEEPS,
          repeats: int = REPEATS, round_trips: int = ROUND_TRIPS) -> dict:
    """One reading: {"tflops": least-time rate of the chain, "tflops_all",
    "gbps", "sync_us", "probe_s"}. Everything it allocated is freed on
    return."""
    import numpy as np
    operands, chain, array, sweep, tiny = _programs()
    t_begin = time.perf_counter()
    a, b = operands(n)
    _timed(chain, a, b, np.int32(1))            # loads the executable
    secs = []
    for _ in range(repeats):
        s, out = _timed(chain, a, b, np.int32(chain_len))
        out.delete()
        secs.append(s)
    a.delete()
    b.delete()
    x = array(nbytes // 4)
    _, x = _timed(sweep, x, np.int32(1))
    sweep_s = []
    for _ in range(2):
        s, x = _timed(sweep, x, np.int32(sweeps))
        sweep_s.append(s)
    x.delete()
    v = array(8)
    trips = []
    for _ in range(1 + round_trips):
        s, v = _timed(tiny, v)
        trips.append(s)
    v.delete()
    flops = matmul_flops(n, chain_len)
    return {"tflops": flops / min(secs) / 1e12,
            "tflops_all": [flops / s / 1e12 for s in secs],
            "gbps": sweep_traffic_bytes(nbytes, sweeps) / min(sweep_s) / 1e9,
            "sync_us": 1e6 * statistics.median(trips[1:]),
            "probe_s": time.perf_counter() - t_begin}


def is_slow(reading: dict, first: dict) -> bool:
    """A reading against the process's first one."""
    return any(reading[k] < first[k] * under
               for k, under in SLOW_UNDER.items())


def step_ms_thirds(starts: Sequence[float], seconds: Sequence[float],
                   w0: float, w1: float) -> List[Optional[float]]:
    """The median step time (ms) of the steps that START in each third
    of the window [w0, w1): a change of state inside a run shows as
    thirds that disagree."""
    third = (w1 - w0) / 3.0
    parts = [[], [], []]
    for t, s in zip(starts, seconds):
        if w0 <= t < w1:
            parts[min(2, int((t - w0) / third))].append(s)
    return [1e3 * statistics.median(p) if p else None for p in parts]


def thirds_disagree(thirds: Sequence[Optional[float]]) -> bool:
    have = [t for t in thirds if t]
    return len(have) > 1 and max(have) > min(have) * (1.0 + THIRDS_APART)


def window_state(slow_around: Sequence[bool],
                 thirds: Sequence[Optional[float]] = ()) -> str:
    """The state of a window from the readings taken around it (is each
    slow: before the ramp, after the drain) and from its thirds."""
    if len(set(slow_around)) != 1 or thirds_disagree(thirds):
        return "mixed"
    return "slow" if slow_around[0] else "fast"


def gives_up(slow: bool, elapsed_s: float, spent_s: float) -> bool:
    """The gate: a process that reads slow before its ramp, `elapsed_s`
    after it started, gives its window up if that and the `spent_s` of
    the starts given up before it stay inside the budget."""
    return slow and spent_s + elapsed_s <= GATE_BUDGET_S


class ChipState:
    """A run's readings, and the gate. `rehearse`: the probe runs at a
    tiny size (the code path, on a CPU), no reading is kept under a
    device's name and nothing is given up. `start` counts the processes
    started for this run, `spent_s` what the earlier ones took. `gate`
    False (a traced run, whose reading of the trace leaves no room
    under the run's time limit): the window runs whatever the probe
    reads, and says so."""

    def __init__(self, rehearse: bool = False, t_start: float = 0.0,
                 start: int = 1, spent_s: float = 0.0, gate: bool = True,
                 take=probe):
        self.rehearse = rehearse
        self.gate = gate and not rehearse
        self.t_start = t_start
        self.start = start
        self.spent_s = spent_s
        self._take = take
        self.readings = []      # (point, seconds since start, reading)

    def take(self, point: str) -> dict:
        r = self._take(n=128, chain_len=2, nbytes=2 ** 16, sweeps=2,
                       repeats=1, round_trips=4) if self.rehearse \
            else self._take()
        at_s = time.perf_counter() - self.t_start
        self.readings.append((point, at_s, r))
        if point == POINTS[1] and self.gate and gives_up(
                is_slow(r, self.readings[0][2]), at_s, self.spent_s):
            raise SlowChip(point)
        return r

    def _of(self, point: str, key: str = None):
        if self.rehearse:
            return None
        r = next((r for p, _, r in self.readings if p == point), None)
        return r if r is None or key is None else r[key]

    def tflops_of_window(self) -> Optional[float]:
        """The lower of the two readings around the window: the number a
        traced run reports beside its `step_ms.*`."""
        around = [self._of(p, "tflops") for p in POINTS[1:]]
        return None if None in around else min(around)

    def summary(self, thirds: Sequence[Optional[float]] = ()) -> dict:
        first, around = self._of(POINTS[0]), [
            self._of(p) for p in POINTS[1:]]
        if first is None or around[0] is None:
            state = "not_measured"
        elif around[1] is None:         # a start that gave its window up
            state = "slow" if is_slow(around[0], first) else "fast"
        else:
            state = window_state([is_slow(r, first) for r in around],
                                 thirds)
        out = {k: [self._of(p, k) for p in POINTS]
               for k in ("tflops", "gbps", "sync_us")}
        out.update(probe_s=sum(r["probe_s"] for _, _, r in self.readings),
                   at_s=[t for _, t, _ in self.readings],
                   attempts=self.start, gate_spent_s=self.spent_s,
                   state=state, step_ms_thirds=list(thirds))
        if self.rehearse:       # a CPU's step times are no device's
            out.update(rehearsal=True, step_ms_thirds=[None] * 3)
        return out
