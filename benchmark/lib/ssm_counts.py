"""Bytes the state-space layers and the three kinds of cache of a
hybrid decoder move, from shapes (beside lib/moe_counts.py): what the
program's `state_bytes` counter and `cache_bytes_*` numbers are held
to (tests/test_phi4flash.py).
"""


def state_bytes_per_seq(layers: int, d_inner: int, d_state: int,
                        d_conv: int, tail_itemsize: int = 2) -> int:
    """One sequence's f32 scan states and its convolution tails."""
    return layers * (d_inner * d_state * 4
                     + (d_conv - 1) * d_inner * tail_itemsize)


def scan_step_bytes(runs: int, layers: int, d_inner: int, d_state: int,
                    d_conv: int, tail_itemsize: int = 2) -> int:
    """What one step's scans read and write of the slabs: a state and a
    tail in, a state and a tail out, for every run (segment) and
    layer."""
    return 2 * runs * state_bytes_per_seq(layers, d_inner, d_state,
                                          d_conv, tail_itemsize)


def kv_bytes_per_token(kv_heads: int, head_dim: int, itemsize: int = 2,
                       layers: int = 1) -> int:
    """K and V of one token in the paged layers."""
    return 2 * layers * kv_heads * head_dim * itemsize


def ring_bytes_per_seq(layers: int, window: int, chunk: int, page: int,
                       kv_heads: int, head_dim: int,
                       itemsize: int = 2) -> int:
    """A slot's ring of window keys: the pages that cover any window +
    chunk - 1 consecutive positions."""
    pages = -(-(window + chunk - 2) // page) + 1
    return pages * page * kv_bytes_per_token(kv_heads, head_dim, itemsize,
                                             layers)
