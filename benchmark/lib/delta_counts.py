"""Bytes and operations of the gated delta rule's layers, from shapes
(beside lib/ssm_counts.py and lib/moe_counts.py): what the program's
`state_bytes` counter is held to (tests/test_olmo_hybrid.py) and what a
traced run's seconds under `delta_scan` are read against by hand
(`delta_scan_hbm_share.*`: PERF.md question 33).

Heads Hv of a Dk x Dv f32 state each; the convolution runs over the
2 Hk Dk + Hv Dv channels of q, k and v and keeps its last taps - 1 raw
rows as a sequence's tail. The bytes are LOGICAL: a slab whose layout
pads its rows in HBM moves more than is counted here, and reads a lower
share for it, as it should.
"""


def channels(key_heads: int, value_heads: int, dk: int, dv: int) -> int:
    """What the convolution runs over: all heads' q, k and v."""
    return 2 * key_heads * dk + value_heads * dv


def state_bytes_per_seq(layers: int, key_heads: int, value_heads: int,
                        dk: int, dv: int, taps: int = 4,
                        tail_itemsize: int = 2) -> int:
    """One sequence's f32 matrix states and its convolution tails."""
    return layers * (value_heads * dk * dv * 4 + (taps - 1) * channels(
        key_heads, value_heads, dk, dv) * tail_itemsize)


def scan_step_bytes(runs: int, layers: int, key_heads: int,
                    value_heads: int, dk: int, dv: int, taps: int = 4,
                    tail_itemsize: int = 2) -> int:
    """What one step's rule reads and writes of the slabs: a state and
    a tail in, a state and a tail out, for every run and layer."""
    return 2 * runs * state_bytes_per_seq(
        layers, key_heads, value_heads, dk, dv, taps, tail_itemsize)


def lane_flops(value_heads: int, dk: int, dv: int) -> int:
    """One lane of the lane form, a layer: the decay, S^T k, the
    rank-one update and S^T q over every element of the state."""
    return 7 * value_heads * dk * dv


def chunk_block_flops(value_heads: int, dk: int, dv: int,
                      block: int = 64) -> int:
    """The products of ONE chunk-form block of `block` lanes, a layer
    (the WY form of ops/gated_delta._chunk): K K^T and Q K^T (2 C^2 Dk
    each), the triangular inverse by halves (its 2 log2(C) small
    products, under 2 C^3), T (beta K) and T (beta V) (2 C^2 (Dk +
    Dv)), W S, Q S and the state's K^T V' (2 C Dk Dv each), the inner
    (Q K^T) V' (2 C^2 Dv)."""
    c = block
    return value_heads * (
        2 * 2 * c * c * dk + 2 * c ** 3 + 2 * c * c * (dk + dv)
        + 3 * 2 * c * dk * dv + 2 * c * c * dv)
