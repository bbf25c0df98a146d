"""Launcher — `python -m flexflow_tpu [options] script.py [args]`.

The TPU-native analog of the reference's `flexflow_python` interpreter
binary + `flexflow.py` launcher (python/main.cc:91-107 registers the
Python top-level task; flexflow/core/flexflow_top.py:164-220 runs the
user script in script / -c / REPL modes; python/flexflow.py translates
--nodes/--gpus into Legion -ll:* flags).  Here there is no embedded
interpreter to bootstrap — JAX is single-controller — so the launcher's
job is platform setup + script execution:

  python -m flexflow_tpu train.py -b 64 --search-budget 1000
  python -m flexflow_tpu -c "import flexflow_tpu; print(flexflow_tpu.__name__)"
  python -m flexflow_tpu --cpu-devices 8 train.py   # virtual CPU mesh

Launcher-only flags (consumed before the script sees argv):
  --cpu-devices N     force the CPU platform with N virtual devices — the
                      test rig for multi-chip sharding without TPUs
  --coordinator A:P   multi-host: jax.distributed coordinator address
                      (the analog of the reference's mpirun bootstrap,
                      python/flexflow.py — one process per host, Legion
                      control replication → JAX multi-controller SPMD)
  --num-processes N   multi-host: total process count
  --process-id I      multi-host: this process's rank
  -c CODE             run a code string instead of a script
Everything else is left on sys.argv for FFConfig.from_args().
"""

from __future__ import annotations

import os
import runpy
import sys


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)

    if argv and argv[0] in ("--help", "-h"):
        print("usage: flexflow-tpu [--cpu-devices N] "
              "[--coordinator HOST:PORT --num-processes N --process-id I] "
              "(SCRIPT [ARGS...] | -c CODE | <no args for REPL>)\n\n"
              "Runs a user script under the flexflow_tpu runtime "
              "(reference: flexflow_python / python/flexflow.py launcher).")
        return 0

    cpu_devices = None
    code = None
    coordinator = num_processes = process_id = None
    i = 0
    while i < len(argv):
        if argv[i] == "--cpu-devices" and i + 1 < len(argv):
            cpu_devices = int(argv[i + 1])
            del argv[i:i + 2]
        elif argv[i] == "--coordinator" and i + 1 < len(argv):
            coordinator = argv[i + 1]
            del argv[i:i + 2]
        elif argv[i] == "--num-processes" and i + 1 < len(argv):
            num_processes = int(argv[i + 1])
            del argv[i:i + 2]
        elif argv[i] == "--process-id" and i + 1 < len(argv):
            process_id = int(argv[i + 1])
            del argv[i:i + 2]
        elif argv[i] == "-c" and i + 1 < len(argv):
            code = argv[i + 1]
            del argv[i:i + 2]
        else:
            break

    if coordinator is not None:
        # outside auto-detecting cluster environments (GKE/SLURM), JAX
        # cannot infer these; fail with a launcher error, not a deep
        # jax.distributed traceback (reference launcher python/flexflow.py
        # derives ranks from mpirun for the same reason)
        if num_processes is None or process_id is None:
            print("flexflow_tpu: --coordinator requires --num-processes "
                  "and --process-id (they are only auto-detected inside "
                  "cluster environments like SLURM/GKE)", file=sys.stderr)
            return 2
        import jax
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)

    if cpu_devices is not None:
        kept = [f for f in os.environ.get("XLA_FLAGS", "").split()
                if not f.startswith("--xla_force_host_platform_device_count")]
        os.environ["XLA_FLAGS"] = " ".join(
            kept + [f"--xla_force_host_platform_device_count={cpu_devices}"])
        import jax
        # jax may already be imported (its config read JAX_PLATFORMS
        # then), so the config — not the environment — is what still
        # decides before the first backend use
        jax.config.update("jax_platforms", "cpu")

    # the launcher is an entry point: place JAX's persistent compile
    # cache (JAX_COMPILATION_CACHE_DIR, else the checkout's .scratch/)
    from .utils.cache_dirs import arm_compile_cache
    arm_compile_cache()

    if code is not None:
        sys.argv = ["-c"] + argv
        exec(compile(code, "<string>", "exec"), {"__name__": "__main__"})
        return 0

    if not argv:
        # REPL mode (reference flexflow_top.py run_repl)
        import code as code_mod
        code_mod.interact(banner="flexflow_tpu interactive shell")
        return 0

    script, script_args = argv[0], argv[1:]
    sys.argv = [script] + script_args
    sys.path.insert(0, os.path.dirname(os.path.abspath(script)))
    runpy.run_path(script, run_name="__main__")
    return 0


if __name__ == "__main__":
    sys.exit(main())
