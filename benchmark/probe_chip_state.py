#!/usr/bin/env python3
"""Ask the chip what sets its state (PERF.md section 6, PR 42: E1, E2).

    python3 benchmark/probe_chip_state.py e1 [--processes 16]
    python3 benchmark/probe_chip_state.py e2 [--phase-s 120]
    python3 benchmark/probe_chip_state.py one [--probes 5 --over-s 10]
    python3 benchmark/probe_chip_state.py burn [--burn-s 240 --phase-s 120]

e1   fresh processes one after another, each: start JAX, take the probe
     (lib/chip_state.py) `--probes` times over `--over-s` seconds, exit.
     Is the state drawn per process, or does it run in streaks across
     processes? This process stays off JAX: a chip belongs to one
     process at a time.
e2   ONE process: build `phi4flash-reason`'s engine, then probe every
     `--every-s` seconds through three phases of `--phase-s` seconds:
     idle, under the cell's own traffic (with the median step time
     between two probes), idle again. Does the state change in time, and
     does load move it?
one  what e1 runs in each process.
burn ONE process: the probe's matmul chain back to back for `--burn-s`
     seconds, then idle for `--phase-s`, probing. Does sustained
     compute-bound load move the state, and does rest move it back?
`--rehearse-cpu` walks the same code here at a tiny size.

Every reading goes to stdout as one JSON line and, at the end, whole to
`chiprun_out/chip_state_<experiment>.json`. Needs the chip.
"""

import time
T_PROCESS_START = time.perf_counter()

import argparse          # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import statistics        # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)


def say(obj) -> None:
    print(json.dumps(obj, default=float), flush=True)


def keep(name: str, obj) -> None:
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"chip_state_{name}.json"), "w") as f:
        json.dump(obj, f, indent=1, default=float)


def start_jax(rehearse: bool) -> dict:
    """JAX on the chip with the checkout's compile cache armed, as
    run.py does. -> the device as JAX reports it."""
    import jax
    from flexflow_tpu.utils.cache_dirs import arm_compile_cache
    dev = jax.devices()[0]
    if (dev.platform == "tpu") == rehearse:
        raise SystemExit(f"probe_chip_state: JAX found {dev}; the chip "
                         f"is for measuring, --rehearse-cpu for the CPU")
    arm_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return {"platform": dev.platform, "kind": dev.device_kind}


def reading(chip, point: str) -> dict:
    r = chip.take(point)
    return dict(r, point=point, at_s=chip.readings[-1][1])


def one(args) -> int:
    from lib.chip_state import ChipState
    device = start_jax(args.rehearse_cpu)
    chip = ChipState(args.rehearse_cpu, T_PROCESS_START)
    gap = args.over_s / max(1, args.probes - 1)
    first = time.perf_counter()
    rows = []
    for i in range(args.probes):
        time.sleep(max(0.0, first + i * gap - time.perf_counter()))
        rows.append(reading(chip, f"probe{i}"))
    say({"device": device, "readings": rows})
    return 0


def e1(args) -> int:
    t0 = time.time()
    rows = []
    for i in range(args.processes):
        began = time.time() - t0
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "one",
             "--probes", str(args.probes), "--over-s", str(args.over_s)]
            + ["--rehearse-cpu"] * args.rehearse_cpu,
            stdout=subprocess.PIPE, text=True)
        if p.returncode:
            print(f"probe_chip_state: process {i} exited {p.returncode}",
                  file=sys.stderr)
            return p.returncode
        row = json.loads(p.stdout.strip().splitlines()[-1])
        row.update(process=i, began_s=began, ended_s=time.time() - t0)
        rows.append(row)
        say({"process": i, "began_s": began,
             "tflops": [r["tflops"] for r in row["readings"]],
             "gbps": [r["gbps"] for r in row["readings"]]})
    keep("e1", rows)
    return 0


def e2(args) -> int:
    import importlib
    from lib import serving, traffic_gen
    from lib.chip_state import ChipState
    from lib.spans import Spans
    device = start_jax(args.rehearse_cpu)
    chip = ChipState(args.rehearse_cpu, T_PROCESS_START)
    rows = [dict(reading(chip, "start"), phase="start")]
    say(rows[-1])

    from run import load_json, merge
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    conf = load_json(ROOT, next(c["file"] for c in bench["configs"]
                                if c["name"] == cell["config"]))
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if args.rehearse_cpu:
        conf = merge(conf, conf.get("rehearsal", {}))
        traffic = merge(traffic, traffic.get("rehearsal", {}))
    cell_lib = importlib.import_module("lib." + args.cell_lib)
    eng, warmup_s = cell_lib.build_engine(conf, args.seed,
                                          args.rehearse_cpu)
    reqs = traffic_gen.make_requests(traffic, args.seed, conf["vocab_size"],
                                     int(traffic["pool_requests"]))
    rows.append(dict(reading(chip, "built"), phase="built",
                     warmup_s=warmup_s))
    say(rows[-1])

    def idle(phase):
        end = time.perf_counter() + args.phase_s
        while time.perf_counter() < end:
            time.sleep(max(0.0, min(args.every_s,
                                    end - time.perf_counter())))
            rows.append(dict(reading(chip, phase), phase=phase))
            say(rows[-1])

    idle("idle1")
    loop = serving.ServeLoop(eng, Spans())
    state = {"next": time.perf_counter() + args.every_s, "steps_seen": 0}

    def tick(now, w0, w1):
        if now < state["next"]:
            return
        steps = loop.steps[state["steps_seen"]:]
        state["steps_seen"] = len(loop.steps)
        row = dict(reading(chip, "load"), phase="load", steps=len(steps),
                   lanes_mean=statistics.fmean(s[2] for s in steps)
                   if steps else None,
                   step_ms_median=1e3 * statistics.median(
                       b - a for a, b, *_ in steps) if steps else None)
        rows.append(row)
        say(row)
        state["next"] = time.perf_counter() + args.every_s

    serving.run_open_loop(loop, reqs, 0.0, args.phase_s, 0.0, tick)
    loop.close()
    idle("idle2")
    eng.close()
    keep("e2", {"device": device, "workload": args.workload,
                "readings": rows})
    return 0


def burn(args) -> int:
    """The matmul chain back to back for `--burn-s` seconds (the rate of
    every call, summed up every five seconds), then idle, probing."""
    import numpy as np
    from lib import chip_state as cs
    device = start_jax(args.rehearse_cpu)
    chip = cs.ChipState(args.rehearse_cpu, T_PROCESS_START)
    rows = [dict(reading(chip, "start"), phase="start")]
    say(rows[-1])
    size = dict(n=128, chain=2) if args.rehearse_cpu \
        else dict(n=cs.MATMUL_N, chain=cs.MATMUL_CHAIN)
    operands, chain = cs._programs()[:2]
    a, b = operands(size["n"])
    steps = np.int32(size["chain"])
    flops = cs.matmul_flops(**size)
    end = time.perf_counter() + args.burn_s
    while time.perf_counter() < end:
        t_row, rates = time.perf_counter(), []
        while time.perf_counter() < min(end, t_row + 5.0):
            s, out = cs._timed(chain, a, b, steps)
            out.delete()
            rates.append(flops / s / 1e12)
        rows.append({"phase": "burn", "at_s": t_row - T_PROCESS_START,
                     "calls": len(rates), "tflops_min": min(rates),
                     "tflops_median": statistics.median(rates),
                     "tflops_max": max(rates)})
        say(rows[-1])
    a.delete()
    b.delete()
    end = time.perf_counter() + args.phase_s
    while time.perf_counter() < end:
        time.sleep(args.every_s)
        rows.append(dict(reading(chip, "idle"), phase="idle"))
        say(rows[-1])
    keep("burn", {"device": device, "readings": rows})
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("experiment", choices=("e1", "e2", "one", "burn"))
    ap.add_argument("--burn-s", type=float, default=240.0)
    ap.add_argument("--processes", type=int, default=16)
    ap.add_argument("--probes", type=int, default=5)
    ap.add_argument("--over-s", type=float, default=10.0)
    ap.add_argument("--phase-s", type=float, default=120.0)
    ap.add_argument("--every-s", type=float, default=10.0)
    ap.add_argument("--workload", default="phi4flash-reason")
    ap.add_argument("--cell-lib", default="phi4flash_cell")
    ap.add_argument("--seed", type=int, default=4200000001)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="the control flow at a tiny size; no reading "
                         "is a device's")
    args = ap.parse_args()
    return {"e1": e1, "e2": e2, "one": one,
            "burn": burn}[args.experiment](args)


if __name__ == "__main__":
    sys.exit(main())
