"""Operations and bytes of an expert layer's step, from its counts.

`counts` is (layers, experts): the slots (a live lane's one of k
experts) each expert of each layer took in the step, as the engine's
step returns them. A slot passes through one gated expert: three
matmuls of hidden x width. An expert that took at least one slot has
its three matrices read once (the least traffic of the phase that
binds it: what an ideal fused expert needs, not what a given kernel
does).

Convention as lib/flops.py: one multiply-add is 2 operations.
"""

from __future__ import annotations

import numpy as np


def step_work(counts, hidden: int, width: int, itemsize: int) -> dict:
    counts = np.asarray(counts)
    slots = int(counts.sum())
    touched = int((counts > 0).sum())
    expert_weights = 3 * hidden * width
    return {
        "slots": slots, "touched": touched,
        "flops": 2.0 * slots * expert_weights,
        "weight_bytes": float(touched * expert_weights * itemsize),
    }

