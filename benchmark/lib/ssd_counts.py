"""Bytes and operations of the Mamba-2 heads' layers, from shapes
(beside lib/ssm_counts.py and lib/delta_counts.py): what the program's
`state_bytes` counter is held to (tests/test_falcon_h1.py) and what a
traced run's seconds under `ssm_scan` are read against
(`ssm_scan_hbm_share.phi`; the chunk form's share of the MXU peak by
hand, PERF.md section 5).

H heads of a P x N f32 state each, G groups of N for B and C; the
convolution runs over the H P + 2 G N channels of x, B and C and keeps
its last taps - 1 raw rows as a sequence's tail.
"""


def channels(heads: int, head_dim: int, groups: int, d_state: int) -> int:
    """What the convolution runs over: x, B and C."""
    return heads * head_dim + 2 * groups * d_state


def state_bytes_per_seq(layers: int, heads: int, head_dim: int,
                        groups: int, d_state: int, taps: int = 4,
                        tail_itemsize: int = 2) -> int:
    """One sequence's f32 matrix states and its convolution tails."""
    return layers * (heads * head_dim * d_state * 4 + (taps - 1) * channels(
        heads, head_dim, groups, d_state) * tail_itemsize)


def scan_step_bytes(runs: int, layers: int, heads: int, head_dim: int,
                    groups: int, d_state: int, taps: int = 4,
                    tail_itemsize: int = 2) -> int:
    """What one step's recurrence reads and writes of the slabs: a
    state and a tail in, a state and a tail out, for every run and
    layer."""
    return 2 * runs * state_bytes_per_seq(
        layers, heads, head_dim, groups, d_state, taps, tail_itemsize)


def lane_flops(heads: int, head_dim: int, d_state: int) -> int:
    """One lane of the lane form, a layer: the decay, the rank-one
    update and S C over every element of the state."""
    return 5 * heads * head_dim * d_state


def chunk_block_flops(heads: int, head_dim: int, groups: int,
                      d_state: int, block: int = 64) -> int:
    """The products of ONE chunk-form block of `block` lanes, a layer
    (ops/ssd._chunk): C B^T a group (2 C^2 N), (C B^T * L) V a head
    (2 C^2 P), C S and the state's B^T V (2 C N P a head each)."""
    c = block
    return groups * 2 * c * c * d_state + heads * (
        2 * c * c * head_dim + 2 * 2 * c * d_state * head_dim)
