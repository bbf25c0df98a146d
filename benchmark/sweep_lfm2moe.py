#!/usr/bin/env python3
"""The rate sweep behind `lfm2moe-longanswer`'s `rate_rps`: ONE engine
of the configuration, the cell's traffic offered at each of `--rates`
for a ramp and a window, the requests still open cancelled between
rates. One line a rate: time to first token, queue wait, token gap,
tokens a second, lanes in use, the median step's decode lanes and
sequences (the slots in use), the share of steps that held a whole chunk
of prefill lanes, and what still ran and waited at the window's end —
the highest rate at which nothing waits is the knee
(traffic/lfm2moe-longanswer.json `knee_why` holds the readings). An
answer of 512 tokens lives 13 s, so a window reads its rate only after a
ramp of that length or more: the traffic's 30 s.

    python3 benchmark/sweep_lfm2moe.py --seed <n> --rates 8,10,12,14,16 [--seconds 60]
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rates", default="8,10,12,14,16")
    ap.add_argument("--seconds", type=float, default=60.0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    from run import load_json, merge
    conf = load_json(HERE, "configs", "lfm2-24b-a2b-1chip-l10.json")
    traffic = load_json(HERE, "traffic", "lfm2moe-longanswer.json")
    if args.rehearse_cpu:
        conf = merge(conf, conf["rehearsal"])
        traffic = merge(traffic, traffic["rehearsal"])

    import numpy as np
    from flexflow_tpu.utils.cache_dirs import arm_compile_cache
    from lib import lfm2moe_cell, serving, traffic_gen
    from lib.spans import Spans
    arm_compile_cache()
    eng, warm_s = lfm2moe_cell.build_engine(conf, args.seed,
                                            args.rehearse_cpu)
    print(f"# engine: warm in {warm_s:.1f} s, {eng.mixed_width} lanes, "
          + json.dumps({k: v for k, v in eng.boot_stats.items()
                        if k.startswith("conv_tail")}), flush=True)
    loop = lfm2moe_cell.Loop(eng, Spans())
    ramp = float(traffic["ramp_s"])
    pct = lambda xs, q: float(np.percentile(xs, q)) * 1e3 if xs else None
    for i, rate in enumerate(map(float, args.rates.split(","))):
        t = dict(traffic, rate_rps=rate)
        reqs = traffic_gen.make_requests(
            t, args.seed + i, conf["vocab_size"], int(t["pool_requests"]))
        w = serving.run_open_loop(loop, reqs, ramp, args.seconds, 0.0)
        num = serving.window_numbers(loop, w, True)
        counts = lfm2moe_cell.window_step_counts(loop, w,
                                                 eng.prefill_budget)
        mine = [r for r in loop.records if not r.done
                and r.t_submit is not None and r.t_submit >= w["w0"] - ramp]
        print("# rate: " + json.dumps({
            "rate_rps": rate, "attempted": num["attempted"],
            "ttft_p50_ms": pct(num["ttft_s"], 50),
            "queue_wait_p95_ms": pct(num["queue_wait_s"], 95),
            "tpot_p50_ms": pct(num["gaps_s"], 50),
            "tpot_p95_ms": pct(num["gaps_s"], 95),
            "step_p50_ms": pct(num["step_s"], 50),
            "gen_tokens_per_s": num["gen_tokens"] / num["seconds"],
            "prompt_tokens_per_s":
                num["prompt_tokens_served"] / num["seconds"],
            "lanes_in_use": float(np.mean(num["lane_occupancy"])),
            "decode_lanes_per_step": num["decode_lanes"] / num["steps"],
            "decode_lanes_p50": counts.get("decode_lanes_p50"),
            "slots_in_use_p50": counts.get("seqs_in_step_p50"),
            "slots_in_use_max": counts.get("seqs_in_step_max"),
            "whole_chunk_step_share": counts.get("whole_chunk_step_share"),
            "decode_only_step_share": counts.get("decode_only_step_share"),
            "running_at_end": sum(r.t_admit is not None for r in mine),
            "waiting_at_end": sum(r.t_admit is None for r in mine)}),
            flush=True)
        for r in mine:
            eng.cancel(r.handle.rid)
        while loop.session.has_work():
            loop.step()
    loop.close()
    eng.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
