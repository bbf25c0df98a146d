"""Inception-v3 conv audit: where does the MFU go? (VERDICT r2 #3)

Prints, for the bench config (299px, bf16):
  1. the analytic per-op table (utils/profiling.op_profile);
  2. XLA's own cost analysis of the compiled train step per conv
     layout (NCHW vs NHWC) — flops, bytes, and the flops/byte the
     compiled program actually has after fusion;
  3. a tiling audit: convs whose channel counts miss the 128-lane MXU
     tile or whose odd spatial dims (299 -> 149 -> 74...) force
     padding, the usual culprits for conv MFU well below the GEMM
     fraction (reference conv_2d.cu:173-260 works around the cuDNN
     analog with per-shape algorithm selection);
  4. measured ms/step per layout when the backend is usable.

Run on a TPU for the timed A/B; on CPU it still prints 1-3 with a
small image size.
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    import jax

    from flexflow_tpu.utils import profiling

    import bench  # the SAME config the bench measures — no drift

    on_cpu = jax.devices()[0].platform == "cpu"
    preset = "tiny" if on_cpu else "full"

    def build(layout):
        os.environ["BENCH_CONV_LAYOUT"] = layout
        return bench.build("inception", preset)

    ff, data = build("NCHW")
    batch, size = data["input"].shape[0], data["input"].shape[-1]

    # ---- 3. tiling audit (static, layout-independent) ----
    print("=== tiling audit: convs vs the (8, 128) TPU tile ===")
    flagged = 0
    for op in ff.ops:
        if op.op_type != "conv2d":
            continue
        n, c_in, h, w = op.inputs[0].shape
        c_out = op.out_channels
        notes = []
        if c_in % 128 and c_in > 16:
            notes.append(f"cin {c_in} % 128 != 0")
        if c_out % 128:
            notes.append(f"cout {c_out} % 128 != 0")
        if h % 2 or w % 2:
            notes.append(f"odd spatial {h}x{w} (stride pads)")
        if notes:
            flagged += 1
            print(f"  {op.name:28s} ({c_in:4d}->{c_out:4d}, {h}x{w}): "
                  + "; ".join(notes))
    print(f"  {flagged} convs flagged")

    # ---- 1. analytic table ----
    print("\n=== analytic per-op profile (top of the table) ===")
    print("\n".join(profiling.op_profile(ff).splitlines()[:20]))

    # ---- 2 + 4. per-layout compiled cost + measured time ----
    # (CPU: one layout only — a second full inception compile takes
    # minutes and the layout knob is a TPU question)
    results = {}
    for layout in (("NCHW",) if on_cpu else ("NCHW", "NHWC")):
        ffl = ff if layout == "NCHW" else build(layout)[0]
        cost = profiling.hlo_cost(ffl, data)
        entry = {"xla_flops": cost.get("flops"),
                 "xla_bytes": cost.get("bytes accessed")}
        if entry["xla_flops"] and entry["xla_bytes"]:
            entry["flops_per_byte"] = round(
                entry["xla_flops"] / entry["xla_bytes"], 2)
        try:
            entry["ms_per_step"] = round(
                profiling.time_train_steps(ffl, data, steps=10) * 1e3, 3)
        except Exception as e:  # pragma: no cover - backend-specific
            entry["ms_per_step"] = None
            print(f"  (timing unavailable for {layout}: {e})")
        results[layout] = entry
        print(f"\n=== {layout}: XLA cost analysis ===")
        print(json.dumps(entry))

    print("\n" + json.dumps({"audit": "inception", "batch": batch,
                             "image": size, "layouts": results}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
