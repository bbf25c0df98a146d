#!/usr/bin/env python3
"""Where one start of a benchmark cell goes, phase by phase.

    python3 tools/setup_phases.py --workload <cell> [--cold] [--seed N]

Makes the start `benchmark/run.py` makes for the cell, in the same
order and with the builder functions the cell itself calls
(`benchmark/lib/*_cell.py::build_engine`, `lib/system.py`: imported,
not edited) — `import flexflow_tpu`, the first `jax.devices()`
(`backend_init`), the compile cache armed as run.py arms it, the cell's
driver module imported (the model's modules with it), then the model
and the engine to the end of `warmup()`, or the trainer and its first
step to the fetched loss (`first_step`) — and stops there: no probe, no
traffic, no window. What it prints is what the program recorded of
itself (docs/observability.md "Set-up phases": `PROCESS_PHASES`, the
model's and the engine's `boot_stats["phases"]`) as a tree: each
phase's seconds, its SELF seconds (its own less its children's), what
the process compiled, read from JAX's persistent cache, traced and
lowered while it ran, and the share of the wall time, from this file's
first line to the end, that no phase covers. The last line is the same
as one JSON object.

`--cold` runs the start in a child whose `JAX_COMPILATION_CACHE_DIR` is
an empty temporary directory (this process then stays off JAX).
`--rehearse-cpu` shrinks the configuration to its `rehearsal` group and
interprets the kernels, as run.py's does: for finding faults without
the chip, its seconds are no device's.
"""

import time
T_START = time.perf_counter()

import argparse          # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402
import types             # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
COUNTERS = ("backend_compiles", "backend_compile_s", "cache_hits",
            "trace_s", "lower_s")


def tree_rows(phases: list) -> list:
    """The records of several PhaseLists as rows in start order:
    (depth, name, dur_s, self_s, args). A record's parent is the
    enclosing record of the same name that began last before it."""
    recs = sorted(phases, key=lambda r: (r[2], -r[3]))
    rows, stack = [], []        # stack: indices into rows, outermost first
    for name, parent, t0, dur, args in recs:
        while stack and not (rows[stack[-1]][1] == parent
                             and t0 < rows[stack[-1]][5]):
            stack.pop()
        rows.append([len(stack), name, dur, dur, args or {}, t0 + dur])
        if stack:
            rows[stack[-1]][3] -= dur
        stack.append(len(rows) - 1)
    return [tuple(r[:5]) for r in rows]


def report(phases: list, wall_s: float, head: dict) -> dict:
    rows = tree_rows(phases)
    covered = sum(r[2] for r in rows if r[0] == 0)
    print(f"{'phase':<34}{'s':>9}{'self s':>9}{'compiles':>9}"
          f"{'compile s':>10}{'hits':>6}{'trace s':>9}{'lower s':>9}")
    for depth, name, dur, self_s, args in rows:
        c = [args.get(k, 0) for k in COUNTERS]
        extra = {k: v for k, v in args.items() if k not in COUNTERS}
        print(f"{'  ' * depth + name:<34}{dur:9.3f}{self_s:9.3f}{c[0]:9d}"
              f"{c[1]:10.3f}{c[2]:6d}{c[3]:9.3f}{c[4]:9.3f}  "
              + (json.dumps(extra, default=str) if extra else ""))
    print(f"{'wall (first line to the end)':<34}{wall_s:9.3f}")
    print(f"{'no phase covers':<34}{wall_s - covered:9.3f}"
          f"  ({100 * (1 - covered / wall_s):.1f} % of the wall time)")
    out = dict(head, wall_s=wall_s, covered_s=covered,
               uncovered_share=1 - covered / wall_s,
               rows=[{"depth": d, "name": n, "s": s, "self_s": ss, **a}
                     for d, n, s, ss, a in rows])
    print(json.dumps(out, default=str), flush=True)
    return out


def builder_of(driver: types.ModuleType):
    """What the cell's driver builds its system with: the trainer of
    `lib/system.py` where the driver imports that module itself, else
    the `build_engine` of the `*_cell` module it hands the run to
    (`serve_cell` has none of its own and calls `system.build_engine`).
    -> (kind, function)"""
    if hasattr(driver, "system"):
        return "train", driver.system.build_trainer
    cell = next(m for m in vars(driver).values()
                if isinstance(m, types.ModuleType)
                and m.__name__.endswith("_cell"))
    return "serve", getattr(cell, "build_engine", None) \
        or cell.system.build_engine


def start(args) -> dict:
    sys.path.insert(0, BENCH)
    sys.path.insert(1, ROOT)
    import run as bench_run                 # load_json, merge: run.py's own
    bench = bench_run.load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise SystemExit(f"setup_phases: no workload {args.workload!r}")
    conf = bench_run.load_json(ROOT, next(
        c["file"] for c in bench["configs"] if c["name"] == cell["config"]))
    traffic = bench_run.load_json(BENCH, "traffic",
                                  cell["traffic"] + ".json")
    if args.rehearse_cpu:
        conf = bench_run.merge(conf, conf.get("rehearsal", {}))
        traffic = bench_run.merge(traffic, traffic.get("rehearsal", {}))

    import flexflow_tpu  # noqa: F401  (run.py's order: before jax)
    import jax
    from flexflow_tpu.core.programs import PROCESS_PHASES
    from flexflow_tpu.utils.cache_dirs import arm_compile_cache
    from flexflow_tpu.utils.telemetry import SETUP_THREAD, telemetry_for

    def phase(name, keep=PROCESS_PHASES):
        return telemetry_for().timed(("process", SETUP_THREAD), name,
                                     keep=keep)

    with phase("backend_init"):
        devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" and not args.rehearse_cpu:
        print(f"setup_phases: no TPU (JAX found {device}); nothing was run",
              file=sys.stderr)
        raise SystemExit(3)
    cache_dir, was_empty = arm_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    with phase("import_driver"):
        driver = importlib.import_module("drivers." + traffic["driver"])
    kind, build = builder_of(driver)
    if kind == "serve":
        eng, _ = build(conf, args.seed, args.rehearse_cpu)
        phases = eng.boot_stats["phases"]
        eng.close()
    else:
        from lib import traffic_gen
        tr = conf["train"]
        lm, _ = build(conf, args.seed, devs[:cell["chips"]],
                      lambda what, obj: None)
        batch = traffic_gen.token_batch(
            args.seed, 0, int(tr["global_batch"]),
            conf["max_position_embeddings"], conf["vocab_size"])
        with lm.setup_phase("first_step"):
            float(lm.train_batch(batch)["loss"])
        phases = lm.boot_stats["phases"]
    wall_s = time.perf_counter() - T_START
    return report(list(PROCESS_PHASES) + list(phases), wall_s, {
        "workload": cell["name"], "seed": args.seed, "device": device,
        "compile_cache": {"dir": cache_dir, "was_empty": was_empty},
        "rehearsal": bool(args.rehearse_cpu)})


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cold", action="store_true")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    if not args.cold:
        start(args)
        return 0
    argv = [a for a in sys.argv[1:] if a != "--cold"]
    with tempfile.TemporaryDirectory(prefix="setup_phases_xla_") as d:
        return subprocess.call(
            [sys.executable, os.path.abspath(__file__), *argv],
            env=dict(os.environ, JAX_COMPILATION_CACHE_DIR=d))


if __name__ == "__main__":
    sys.exit(main())
