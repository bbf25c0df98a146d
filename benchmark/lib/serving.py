"""One serving loop for both loops of clients.

A single thread drives `ServeSession`: feed what is due (open loop) or
what an idle client takes next (closed loop), call `step()`, stamp what
it emitted with the host clock after it returns — the client's side.
The same loop runs the ramp before the window (set-up: it fills the
slots and the prefix cache to their steady state), the window, and a
bounded drain after it.

What the window counts:
  open loop   requests DUE in [0, seconds): time from due to first
              token; every gap between consecutive tokens delivered in
              the window
  closed loop prompt tokens served + generated tokens delivered in the
              window. A prompt is served between its submission and its
              first token, and counts by the share of that interval that
              lies in the window: a whole prompt is 2 % of a window's
              work here, so counting it at one instant would let a
              millisecond move the rate by 2 %
"""

from __future__ import annotations

import time
from typing import List, Optional

from .chip_state import step_ms_thirds
from .spans import Spans
from .traffic_gen import Req


class Rec:
    """The client's record of one request."""
    __slots__ = ("req", "due", "t_submit", "t_admit", "t_tokens",
                 "hit_tokens", "handle", "done", "outcome")

    def __init__(self, req: Req, due: Optional[float]):
        self.req = req
        self.due = due              # perf_counter time it was due
        self.t_submit = None
        self.t_admit = None         # start of the first step that ran it
        self.t_tokens: List[float] = []
        self.hit_tokens = 0
        self.handle = None
        self.done = False
        self.outcome = "pending"


class ServeLoop:
    def __init__(self, eng, spans: Spans):
        self.eng = eng
        self.spans = spans
        self.session = eng.start_session()
        self.by_rid = {}
        self.records: List[Rec] = []
        self.steps = []   # (t0, t1, live lanes, prefill lanes, decode lanes)
        self.lateness: List[float] = []

    def submit(self, rec: Rec) -> None:
        with self.spans.span("submit"):
            now = time.perf_counter()
            rec.t_submit = now
            if rec.due is None:
                rec.due = now
            else:
                self.lateness.append(now - rec.due)
            rec.handle = self.session.submit(rec.req.prompt, rec.req.max_new)
            self.by_rid[rec.handle.rid] = rec
            self.records.append(rec)

    def step(self) -> List[Rec]:
        """One engine step; returns the records that finished in it."""
        t0 = time.perf_counter()
        with self.spans.span("step"):
            ev = self.session.step()
        t1 = time.perf_counter()
        finished = []
        if ev is None:
            return finished
        plan = ev.plan
        if plan is not None and plan.chunks:
            for ch in plan.chunks:
                rec = self.by_rid.get(ch.req.rid)
                if rec is not None and rec.t_admit is None:
                    rec.t_admit = t0
                    rec.hit_tokens = int(ch.start)
            if ev.dispatched:
                pre, dec = plan.num_prefill_lanes, plan.num_decode_lanes
                drafts = sum(len(c.draft_tokens or ()) for c in plan.chunks)
                self.steps.append((t0, t1, pre + dec + drafts, pre, dec))
        for req, n in ev.emitted:
            rec = self.by_rid.get(req.rid)
            if rec is not None:
                rec.t_tokens.extend([t1] * n)
        for req in ev.finished:
            rec = self.by_rid.get(req.rid)
            if rec is not None:
                rec.done = True
                rec.outcome = str(getattr(req.outcome, "value", req.outcome))
                finished.append(rec)
        return finished

    def sleep_until(self, t: float) -> None:
        with self.spans.span("generator_sleep"):
            while True:
                left = t - time.perf_counter()
                if left <= 0:
                    return
                time.sleep(min(left, 0.05))

    def close(self) -> dict:
        stats = self.session.stats_dict()
        self.session.close()
        return stats

    def check_records(self) -> List[dict]:
        return [{"prompt": r.req.prompt,
                 "tokens": list(r.handle.out_tokens) if r.handle else [],
                 "hit_tokens": r.hit_tokens, "done": r.done}
                for r in self.records]


def run_open_loop(loop: ServeLoop, reqs: List[Req], ramp_s: float,
                  seconds: float, drain_s: float, tick=None) -> dict:
    """Requests become due at t_start + due_s; the window opens ramp_s
    later. A request is submitted at the first loop turn after it is
    due (the engine admits only between steps, so a later submit would
    change nothing); its latency counts from the due time."""
    if reqs[-1].due_s < ramp_s + seconds:
        raise SystemExit("benchmark: the traffic file's request pool ends "
                         "inside the window; raise pool_requests")
    t_start = time.perf_counter()
    w0 = t_start + ramp_s
    w1 = w0 + seconds
    recs = [Rec(r, t_start + r.due_s) for r in reqs]
    nxt = 0
    while True:
        now = time.perf_counter()
        if tick:
            tick(now, w0, w1)
        while nxt < len(recs) and recs[nxt].due <= now \
                and recs[nxt].due < w1:
            loop.submit(recs[nxt])
            nxt += 1
        feeding = nxt < len(recs) and recs[nxt].due < w1
        if loop.session.has_work():
            loop.step()
        elif feeding:
            loop.sleep_until(min(recs[nxt].due, w1))
        else:
            break
        if now >= w1 + drain_s:
            break
    return {"w0": w0, "w1": w1, "t_end": time.perf_counter()}


def run_closed_loop(loop: ServeLoop, reqs: List[Req], clients: int,
                    ramp_s: float, seconds: float, drain_s: float,
                    tick=None) -> dict:
    """`clients` callers share one list: a caller whose request has
    completed takes the next. Feeding stops at the window's end; what
    is in flight then drains (bounded)."""
    t_start = time.perf_counter()
    w0 = t_start + ramp_s
    w1 = w0 + seconds
    nxt, in_flight = 0, 0
    while True:
        now = time.perf_counter()
        if tick:
            tick(now, w0, w1)
        while in_flight < clients and now < w1:
            if nxt >= len(reqs):
                raise SystemExit("benchmark: the document list ran out "
                                 "inside the window; raise documents")
            loop.submit(Rec(reqs[nxt], None))
            nxt += 1
            in_flight += 1
        if not loop.session.has_work() or now >= w1 + drain_s:
            break
        in_flight -= len(loop.step())
    return {"w0": w0, "w1": w1, "t_end": time.perf_counter()}


def window_numbers(loop: ServeLoop, w: dict, open_loop: bool) -> dict:
    """Everything the end-to-end metrics and the counters' readers need,
    from the client's records and the loop's step log."""
    w0, w1 = w["w0"], w["w1"]
    secs = w1 - w0
    if open_loop:
        mine = [r for r in loop.records if w0 <= r.due < w1]
    else:
        mine = [r for r in loop.records if w0 <= r.t_submit < w1]
    ttft = [r.t_tokens[0] - r.due for r in mine if r.t_tokens]
    gaps = [b - a for r in loop.records
            for a, b in zip(r.t_tokens, r.t_tokens[1:]) if w0 <= b < w1]
    first_in = [r for r in loop.records
                if r.t_tokens and w0 <= r.t_tokens[0] < w1]
    gen_tokens = sum(1 for r in loop.records for t in r.t_tokens
                     if w0 <= t < w1)
    prompt_tokens = sum(len(r.req.prompt) for r in first_in)
    served = 0.0
    for r in loop.records:
        if r.t_tokens and r.t_tokens[0] > r.t_submit:
            a, b = r.t_submit, r.t_tokens[0]
            inside = max(0.0, min(b, w1) - max(a, w0))
            served += len(r.req.prompt) * inside / (b - a)
    steps = [s for s in loop.steps if w0 <= s[0] < w1]
    width = loop.eng.mixed_width
    failed = [r for r in mine if not r.done or r.outcome != "completed"]
    return {
        "seconds": secs, "attempted": len(mine), "failed": len(failed),
        "ttft_s": ttft, "gaps_s": gaps,
        "queue_wait_s": [r.t_admit - r.due for r in mine
                         if r.t_admit is not None],
        "gen_tokens": gen_tokens, "prompt_tokens": prompt_tokens,
        "prompt_tokens_served": served,
        "hit_tokens": sum(r.hit_tokens for r in first_in),
        "step_s": [b - a for a, b, *_ in steps],
        "step_ms_thirds": step_ms_thirds(
            [s[0] for s in steps], [s[1] - s[0] for s in steps], w0, w1),
        "lane_occupancy": [s[2] / width for s in steps],
        "prefill_lanes": sum(s[3] for s in steps),
        "decode_lanes": sum(s[4] for s in steps),
        "lateness_s": loop.lateness, "steps": len(steps),
    }
