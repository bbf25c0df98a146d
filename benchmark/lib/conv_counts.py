"""Bytes and operations of the gated short convolution's layers, from
shapes (beside lib/ssd_counts.py and lib/moe_counts.py): what the
program's `state_bytes` counter is held to (tests/test_lfm2_moe.py) and
what a traced run's seconds under `short_conv` and `conv_proj` are read
against (PERF.md section 5, by hand: no entry of BENCHMARK.json can take
them yet).

E channels; a sequence keeps its last taps - 1 products B * z a layer —
the layer's whole cache: no state, no page.

Convention as lib/flops.py: one multiply-add is 2 operations.
"""


def tail_bytes_per_seq(layers: int, hidden: int, taps: int = 3,
                       itemsize: int = 2) -> int:
    """One sequence's tails over `layers` convolution layers."""
    return layers * (taps - 1) * hidden * itemsize


def step_tail_bytes(runs: int, layers: int, hidden: int, taps: int = 3,
                    itemsize: int = 2) -> int:
    """What one step's convolutions read and write of the tail slab: a
    tail in and a tail out, for every run and layer."""
    return 2 * runs * tail_bytes_per_seq(layers, hidden, taps, itemsize)


def lane_flops(hidden: int, taps: int = 3) -> int:
    """One lane of one layer: the in-projection to 3 E, the gate, the
    taps, the second gate and the out-projection."""
    return 2 * hidden * 3 * hidden + hidden + 2 * taps * hidden + hidden \
        + 2 * hidden * hidden


def proj_bytes(hidden: int, itemsize: int = 2) -> int:
    """The weights one layer's two projections read a step, whatever
    the lanes: W_in (E, 3 E) and W_out (E, E)."""
    return 4 * hidden * hidden * itemsize
