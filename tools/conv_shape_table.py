"""Per-shape conv cost table: measured vs analytic for every distinct
conv signature in the conv-heavy bench models (VERDICT r3 #2 — the
analog of the reference's per-shape cuDNN algorithm selection,
/root/reference/src/ops/conv_2d.cu:173-260).

For each distinct Conv2D signature in Inception-v3 and AlexNet at the
EXACT bench configs (reusing bench.build, so the shapes cannot drift
from what bench.py measures): the measured isolated-kernel fwd+bwd
time (search/op_measure.py — the same memoized measurements
--measure-ops reads, so this run warms the per-machine cache for
unsharded/single-chip searches; data-sharded candidates measure at
their own sub-shape), the analytic roofline prediction, and the
implied achieved MXU fraction. Sorted by measured time: the top rows
are where Inception's MFU lives, and a row whose achieved fraction is
far below the calibrated conv efficiency is a specific shape worth a
layout/padding fix or a Pallas kernel.

Writes evidence/conv_shape_table_<platform>.json. On the chip:
CONV_TABLE_PLATFORM=tpu, one process per chip call.
"""

import json
import os
import sys

import jax

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _platform import select_platform  # noqa: E402

_plat = select_platform("CONV_TABLE_PLATFORM")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from flexflow_tpu.search.machine_model import default_machine_model  # noqa: E402
from flexflow_tpu.search.measure import calibrated_machine_model  # noqa: E402
from flexflow_tpu.search.op_measure import measure_op, op_signature  # noqa: E402


def conv_rows(model, mm, repeats):
    from flexflow_tpu.search.cost_model import op_cost
    from flexflow_tpu.parallel.pconfig import OpStrategy
    from flexflow_tpu.parallel.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    seen = {}
    for op in model.ops:
        if op.op_type != "conv2d":
            continue
        sig = op_signature(op, 1)
        if sig in seen:
            seen[sig]["count"] += 1
            continue
        c = op_cost(op, OpStrategy({}), mesh, mm)
        m = measure_op(op, sample_shard=1, repeats=repeats)
        row = {
            "example_op": op.name,
            "count": 1,
            "in_shape": list(op.inputs[0].shape),
            "out_shape": list(op.outputs[0].shape),
            "flops": op.flops(),
            "analytic_fwd_us": c.fwd * 1e6,
        }
        if m is not None:
            row["measured_fwd_us"] = m["fwd"] * 1e6
            row["measured_bwd_us"] = m["bwd"] * 1e6
            row["achieved_mxu_fraction"] = min(
                1.0, op.flops() / m["fwd"] / mm.spec.peak_flops)
            row["measured_over_analytic"] = m["fwd"] / max(c.fwd, 1e-12)
        seen[sig] = row
    return sorted(seen.values(),
                  key=lambda r: -r.get("measured_fwd_us", 0.0))


def main():
    platform = jax.default_backend()
    mm = (calibrated_machine_model() if platform == "tpu"
          else default_machine_model())
    repeats = 10 if platform == "tpu" else 3
    out = {"platform": platform,
           "conv_efficiency_factor": mm.efficiency.get("conv"),
           "models": {}}
    import bench  # the SAME configs the bench measures — no drift
    # (honors the BENCH_BATCH / BENCH_CONV_LAYOUT knobs too)
    for name in ("inception", "alexnet"):
        model, _data = bench.build(name, "full")
        rows = conv_rows(model, mm, repeats)
        out["models"][name] = rows
        print(f"[{name}] {len(rows)} distinct conv shapes")
        for r in rows[:6]:
            frac = r.get("achieved_mxu_fraction")
            print(f"  {str(r['in_shape']):24s} -> "
                  f"{str(r['out_shape']):24s} x{r['count']:<3d} "
                  f"measured {r.get('measured_fwd_us', float('nan')):9.1f}us"
                  f"  mxu {frac if frac is None else round(frac, 3)}")
    path = os.path.join(os.path.dirname(__file__), "..", "evidence",
                        f"conv_shape_table_{platform}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {os.path.normpath(path)}")


if __name__ == "__main__":
    main()
