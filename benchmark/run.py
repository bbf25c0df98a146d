#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads BENCHMARK.json (the cell, its configuration, its metrics), the
configuration's file, `traffic/<traffic>.json` and, for every metric of
the cell, `metrics/<metric>.json`, which names a reader in `readers/`.
The traffic file's `driver` names the module in `drivers/` that runs the
cell. Nothing here lists cells, configurations or metrics: a later PR
adds files and entries and edits none.

It needs a TPU whose `device_kind` lib/peaks.py knows, with at least the
cell's `chips`; otherwise it exits non-zero and prints no result.
`--rehearse-cpu` is the one exception, for finding faults without the
chip: it shrinks the configuration to its `rehearsal` group, interprets
the kernels, prints the device as it is and an EMPTY `metrics` object —
a CPU number never appears under a metric's name.

Output: progress lines `# <what>: <json>`, then as the last line one
JSON object {correct, attempted, failed, metrics, device[, breakdown]}.
`--trace 0` gives the cell's end-to-end metrics, `--trace 1` its
per-layer metrics, the device's busy seconds and the breakdown.

The command is a parent that stays off JAX (a chip belongs to one
process at a time) and runs the cell in a child, `--start 1`, whose
output and exit code are its own. An untraced child that finds the
chip slow at the end of its set-up (lib/chip_state.py) exits with
EXIT_SLOW before its ramp, and the parent starts another while the
budget lasts; the one that runs the window prints `# chip_state: {...}`
and the result.
`setup_s` counts from the start of the process that ran the window.
"""

import time
T_PROCESS_START = time.perf_counter()

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import signal            # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, ROOT)


@dataclasses.dataclass
class Ctx:
    workload: str
    seed: int
    seconds: float
    chips: int
    conf: dict
    traffic: dict
    trace_dir: object
    rehearse: bool
    spans: object
    t_process_start: float
    chip: object            # lib/chip_state.py: the probe's readings

    @staticmethod
    def say(what: str, obj) -> None:
        print(f"# {what}: {json.dumps(obj, default=float)}", flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


def metrics_of_cell(bench: dict, group: str, cell: str) -> list:
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def read_metric(entry: dict, run: dict):
    spec = load_json(HERE, "metrics", entry["name"] + ".json")
    reader = importlib.import_module("readers." + spec["reader"])
    return reader.read(run, **spec.get("args", {}))


def _die_with_parent() -> None:
    """In the child, before it runs: a parent that is killed takes its
    child with it (Linux; elsewhere the signals below have to do)."""
    try:
        import ctypes
        ctypes.CDLL(None).prctl(1, signal.SIGKILL)  # PR_SET_PDEATHSIG
    except (OSError, AttributeError):
        pass


def run_child(cmd: list) -> int:
    """One child to its end, on this process's own stdout and stderr;
    a signal that ends this process reaches the child first."""
    child = subprocess.Popen(cmd, preexec_fn=_die_with_parent)

    def forward(signum, _frame):
        if child.poll() is None:
            child.send_signal(signum)

    for sig in (signal.SIGTERM, signal.SIGINT, signal.SIGHUP):
        signal.signal(sig, forward)
    return child.wait()


def parent(argv: list, run=run_child, clock=time.perf_counter) -> int:
    """Start the cell in a child until one runs its window; that
    child's exit code is this process's. Each child is told which start
    it is and what the starts before it have cost (the gate's budget is
    lib/chip_state.py's)."""
    from lib.chip_state import EXIT_SLOW
    t0, start = clock(), 0
    while True:
        start += 1
        rc = run([sys.executable, os.path.abspath(__file__), *argv,
                  "--start", str(start),
                  "--gate-spent-s", f"{clock() - t0:.3f}"])
        if rc != EXIT_SLOW:
            return rc


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--start", type=int, default=0,
                    help="set by the parent: which start of this run "
                         "this process is (0: this is the parent)")
    ap.add_argument("--gate-spent-s", type=float, default=0.0,
                    help="set by the parent: seconds the starts given "
                         "up before this one have cost")
    args = ap.parse_args()
    if args.seed < 0:
        raise SystemExit("benchmark: --seed is a non-negative integer")
    if not args.start:
        return parent(sys.argv[1:])

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise SystemExit(f"benchmark: no workload {args.workload!r}")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    conf = load_json(ROOT, cfg_entry["file"])
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if args.rehearse_cpu:
        conf = merge(conf, conf.get("rehearsal", {}))
        traffic = merge(traffic, traffic.get("rehearsal", {}))
    seconds = float(args.seconds if args.seconds is not None
                    else bench["run_seconds"])

    # the program, and the device as JAX reports it. Alone in a
    # directory (no program) this import fails: non-zero, no result.
    import flexflow_tpu  # noqa: F401
    import jax
    from lib import peaks
    from lib.spans import Spans
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if args.rehearse_cpu:
        if device["platform"] == "tpu":
            raise SystemExit("benchmark: --rehearse-cpu is for the CPU")
    elif device["platform"] != "tpu":
        print(f"benchmark: no TPU (JAX found {device}); nothing was run",
              file=sys.stderr)
        return 3
    else:
        peaks.peak_for(device["kind"])        # unknown kind: SystemExit
    if len(devs) < cell["chips"]:
        print(f"benchmark: cell {cell['name']} needs {cell['chips']} chips, "
              f"JAX found {len(devs)}", file=sys.stderr)
        return 3
    Ctx.say("device", device)

    # JAX's persistent compilation cache: the program's own resolver,
    # i.e. JAX_COMPILATION_CACHE_DIR or <checkout>/.scratch/xla_cache;
    # every program is kept, however quick its compile
    from flexflow_tpu.utils.cache_dirs import arm_compile_cache, scratch_dir
    cache_dir, was_empty = arm_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    Ctx.say("compile_cache", {"dir": cache_dir, "was_empty": was_empty})
    from lib.chip_state import EXIT_SLOW, ChipState, SlowChip
    chip = ChipState(args.rehearse_cpu, T_PROCESS_START, args.start,
                     args.gate_spent_s, gate=not args.trace)
    chip.take("start")

    ctx = Ctx(workload=cell["name"], seed=args.seed, seconds=seconds,
              chips=cell["chips"], conf=conf, traffic=traffic,
              trace_dir=(scratch_dir("trace", cell["name"])
                         if args.trace else None),
              rehearse=args.rehearse_cpu,
              spans=Spans(), t_process_start=T_PROCESS_START, chip=chip)
    driver = importlib.import_module("drivers." + traffic["driver"])
    try:
        result = driver.run(ctx)
    except SlowChip:
        Ctx.say("chip_state", dict(chip.summary(), gave_up=True))
        return EXIT_SLOW
    Ctx.say("chip_state", chip.summary(
        result["numbers"].get("step_ms_thirds") or ()))
    result["numbers"]["chip_probe_tflops"] = chip.tflops_of_window()

    used = devs[:cell["chips"]]
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in used)
    device["memory_peak_bytes"] = int(peak_mem)
    trace = result.get("trace") or {}
    run = {"numbers": result["numbers"], "trace": trace,
           "device_kind": None if args.rehearse_cpu else device["kind"],
           "spans": ctx.spans}
    values = {}
    for group in ("end_to_end", "per_layer"):
        for m in metrics_of_cell(bench, group, cell["name"]):
            v = read_metric(m, run)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"],
                                     "group": group}
    Ctx.say("cpu_rehearsal_not_device_metrics" if args.rehearse_cpu
            else "all_metrics", values)
    Ctx.say("numbers", {k: v for k, v in result["numbers"].items()
                        if not isinstance(v, list)})
    group = "per_layer" if args.trace else "end_to_end"
    out = {"correct": bool(result["correct"]),
           "attempted": int(result["attempted"]),
           "failed": int(result["failed"]),
           "metrics": {k: {"value": v["value"], "unit": v["unit"]}
                       for k, v in values.items() if v["group"] == group},
           "device": device}
    if args.trace and trace.get("busy_s"):
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        out["breakdown"] = {"device_ops": trace["device_ops"],
                            "idle_gaps": trace["idle_gaps"]}
    if args.rehearse_cpu:
        out["metrics"] = {}
        out["rehearsal"] = True
        device.pop("busy_s", None)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
