"""Sim-vs-real validation across ALL FIVE bench model families
(VERDICT r3 #6 / weak #8: the <30% claim covered one model).

For each model: build a host-scale config, compile, and run
`FFModel.calibrate_simulator` — which measures real training steps and
returns the simulator's PRE-calibration prediction — twice: analytic
costs only, then with per-op measured grounding
(FFConfig.measure_top_ops, search/op_measure.py). Writes the committed
table evidence/sim_validation_<platform>.json with per-model predicted/
measured/error rows for both modes.

Platform note: on the forced-CPU mesh the machine model's TPU roofline
does not describe the executing hardware, so ANALYTIC error is
expected to be large — what this table demonstrates on CPU is that
per-op MEASURED grounding collapses the error (the mechanism VERDICT
asks for: grounding beats family factors wherever family factors are
wrong). The TPU leg (SIM_VALIDATION_PLATFORM=tpu) produces the on-chip
table against BASELINE.md's <30% envelope.

Run: python tools/sim_validation.py [--quick]
"""

import json
import os
import sys

import jax

# default CPU (the always-available validation platform); the TPU
# session runs with SIM_VALIDATION_PLATFORM=tpu for the on-chip table
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _platform import select_platform  # noqa: E402

_plat = select_platform("SIM_VALIDATION_PLATFORM")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from flexflow_tpu import FFConfig, SGDOptimizer  # noqa: E402
from flexflow_tpu import models as zoo  # noqa: E402


def configs():
    """(name, builder, kwargs, batch) at host-validation scale."""
    return [
        ("alexnet", zoo.build_alexnet, {}, 16),
        ("inception", zoo.build_inception_v3, {}, 4),
        ("dlrm", zoo.build_dlrm,
         {"embedding_vocab_sizes": (10000,) * 8, "embedding_dim": 16,
          "bot_mlp": (64, 16), "top_mlp": (64, 2),
          "stacked_tables": True}, 64),
        ("transformer", zoo.build_transformer,
         {"num_layers": 2, "hidden": 128, "num_heads": 4,
          "ff_dim": 256, "seq_len": 64}, 8),
        ("nmt_lstm", zoo.build_nmt_lstm,
         {"vocab_size": 2000, "embed_dim": 128, "hidden": 128,
          "seq_len": 32, "num_layers": 1}, 16),
    ]


def one(name, builder, kw, batch, measure_ops):
    cfg = FFConfig(batch_size=batch)
    cfg.measure_top_ops = measure_ops
    ff = builder(cfg, **kw)
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type="sparse_categorical_crossentropy", metrics=[])
    measured, predicted = ff.calibrate_simulator(steps=5)
    fingerprint = None
    if ff.simulator is not None:
        # persist the per-op costs under the machine fingerprint so the
        # measured-mode pass (and any re-run of this table) prices from
        # the shared cost cache instead of re-measuring; report the
        # fingerprint the entries were actually written under
        ff.simulator.flush_cost_cache()
        fingerprint = ff.simulator._fingerprint
    if measured < 0.02:
        # sub-20ms steps: 5 steps is inside dispatch-jitter noise (the
        # dlrm row swung -7% -> -41% between otherwise-identical runs);
        # re-measure over enough steps to amortize it
        measured, predicted = ff.calibrate_simulator(steps=200)
    return {"measured_ms": measured * 1e3,
            "predicted_ms": predicted * 1e3,
            "error_pct": 100.0 * (predicted - measured) / measured,
            "fingerprint": fingerprint}


def main():
    quick = "--quick" in sys.argv
    rows = {}
    for name, builder, kw, batch in configs():
        if quick and name == "inception":
            continue  # ~5 min XLA CPU compile
        entry = {}
        # N caps measurement signatures (shape classes). Inception has
        # ~90 DISTINCT conv shapes plus a BatchNorm after every one of
        # them — the budget must reach past the convs into the
        # memory-bound BN/pool/concat signatures or they stay at the
        # (platform-mismatched) analytic price
        deep = 192 if name == "inception" else 8
        for mode, n in (("analytic", 0), ("measured", deep)):
            try:
                entry[mode] = one(name, builder, kw, batch, n)
                print(f"{name:12s} {mode:9s} "
                      f"pred {entry[mode]['predicted_ms']:9.2f} ms  "
                      f"real {entry[mode]['measured_ms']:9.2f} ms  "
                      f"err {entry[mode]['error_pct']:+7.1f}%",
                      flush=True)
            except Exception as e:  # record, keep sweeping
                entry[mode] = {"error": str(e)[:200]}
                print(f"{name:12s} {mode:9s} FAILED: {e}", flush=True)
        rows[name] = entry
    platform = jax.default_backend()
    # stamp the machine-model fingerprint (search/cost_cache.py) the
    # runs' simulators actually keyed their persistent cost-cache
    # entries under: the committed table is attributable to one
    # machine + cost-model state, and re-runs price from that cache
    # instead of re-measuring. Rows carry per-run fingerprints (they
    # should all agree — single-device meshes, one machine); the
    # top-level field is the consensus.
    fps = {e.get("fingerprint") for entry in rows.values()
           for e in entry.values() if e.get("fingerprint")}
    out = {"platform": platform,
           "fingerprint": (fps.pop() if len(fps) == 1
                           else sorted(fps) or None),
           "rows": rows,
           "note": ("CPU: analytic TPU-roofline error is expected; the "
                    "table demonstrates measured grounding collapsing "
                    "it. TPU leg: SIM_VALIDATION_PLATFORM=tpu.")}
    suffix = ""
    if quick:
        # a quick run covers four of the five families — it must not
        # silently shrink the committed five-model table
        out["note"] += " QUICK RUN: inception skipped."
        suffix = "_quick"
    path = os.path.join(os.path.dirname(__file__), "..", "evidence",
                        f"sim_validation_{platform}{suffix}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {os.path.normpath(path)}")


if __name__ == "__main__":
    main()
