"""The gated expert over expert-sorted rows as ONE kernel (Pallas, TPU).

Replaces, in `ops/moe.py::grouped_ffn`, three `jax.lax.ragged_dot` calls
a layer — each a launch of its own that streams all the experts'
weights for a few rows an expert and writes its (S, F) product to HBM
for the next to read (0.83 ms a call at OLMoE's served shape, 38 % of
the chip's bandwidth over the weights; PERF.md section 6, PR 37). Those
three calls stay as this kernel's jnp twin (`ops/moe.py::ragged_ffn`):
the tests hold the two together, and the twin's VJP is the backward.

What a grid step is: one VISIT — a row tile and one expert that holds
live rows in it — by one tile of the expert's width F. It multiplies the
WHOLE row tile by the expert's `wg` and `wu` tiles (f32 accumulation),
gates, zeroes the rows of the tile that belong to another expert, rounds
once and adds the product with the `wd` tile into the row tile's f32
accumulator in VMEM; the tile is written after its last visit. `g`, `u`
and `h` never reach HBM. The visits are in row order, so an expert's
visits are consecutive: with one tile of F its three matrices cross
HBM -> VMEM once however many row tiles it spans (the pipeline does not
fetch a block again whose index did not change), and an expert without
a live row is never visited.

The visit list is built on the device from `counts` (`visit_list`) and
arrives as scalar-prefetch operands; the grid is the most visits there
can be (row tiles + experts - 1), and a dead visit repeats the last live
one's blocks, so it fetches nothing and computes nothing. Row tiles that
hold no live row are never visited: the wrapper zeroes every row past
sum(counts), as the twin does.

Layout contract (`supported`): bf16 rows and weights, D and F multiples
of 128, S a multiple of the row tile. Nothing about a layer is static:
a model's layers make one call with their own weights as operands.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_ragged_v2 import _vmem_limit

F32 = jnp.float32
# rows a visit multiplies, and the widest tile of F a grid step takes.
# On a v5e at OLMoE's served shape (4608 x 2048, 64 experts of 1024;
# tests_tpu/test_grouped_ffn_tpu.py prints the sweep, kept as
# evidence/grouped_ffn_tpu.json; PERF.md section 6,
# PR 37) a layer took, with 320 | 1,104 | 4,608 live rows, at 128 rows
# by all of F 1.02 | 1.15 | 1.40 ms, at 64 rows 1.03 | 1.16 | 1.42, at
# 32 rows 1.06 | 1.22 | 1.65; with F in tiles of 512 (an expert that
# spans two row tiles is fetched twice) 1.04 | 1.22 | 1.69 at 128 rows;
# three `ragged_dot` 2.35 | 2.59 | 2.87, a plain read of the layer's
# 805 MB 1.08
ROW_TILE = 128
MAX_F_TILE = 1024


def supported(rows, wg, *, interpret: bool = False) -> bool:
    """Whether the kernel takes these operands (anything with `shape`
    and `dtype`) where it would run: a tpu backend, or the interpreter
    by argument. Else the twin runs."""
    if not interpret and jax.default_backend() != "tpu":
        return False
    (s, d), f = rows.shape, wg.shape[-1]
    return (rows.dtype == jnp.bfloat16 and wg.dtype == jnp.bfloat16
            and d % 128 == 0 and f % 128 == 0 and s % ROW_TILE == 0)


def choose_f_tile(f: int) -> int:
    """The widest multiple of 128 that divides F, at most MAX_F_TILE."""
    return max(w for w in range(128, min(f, MAX_F_TILE) + 1, 128)
               if f % w == 0)


def visit_list(counts: jax.Array, s: int, tile: int):
    """counts (E,) live rows per expert, in row order -> (offsets
    (E + 1,) the row each expert starts at, tiles (V,), experts (V,),
    n): visit v < n is row tile tiles[v] by expert experts[v], in row
    order; V = S / tile + E. A visit starts wherever a row tile or a
    non-empty expert starts below sum(counts); the visits past n repeat
    visit n - 1."""
    e = counts.shape[0]
    i32 = jnp.int32
    counts = counts.astype(i32)
    ends = jnp.cumsum(counts)
    starts = ends - counts
    total = ends[-1]
    inside = (counts > 0) & (starts % tile != 0)
    bounds = jnp.sort(jnp.concatenate(
        [jnp.arange(s // tile, dtype=i32) * tile,
         jnp.where(inside, starts, s)]))
    n = jnp.sum(bounds < total).astype(i32)
    bounds = jnp.where(jnp.arange(bounds.shape[0]) < n, bounds,
                       bounds[jnp.maximum(n - 1, 0)])
    # the expert that holds row `bound`: the first whose end is past it
    experts = jnp.minimum(
        jnp.searchsorted(ends, bounds, side="right").astype(i32), e - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), i32), ends])
    return offsets, bounds // tile, experts, n


def _ffn_kernel(offsets_ref, tiles_ref, experts_ref, n_ref, x_ref, wg_ref,
                wu_ref, wd_ref, out_ref, acc_ref, *, activation):
    from ..ops.common import apply_activation
    v, f = pl.program_id(0), pl.program_id(1)
    n = n_ref[0]
    tile = tiles_ref[v]
    rows = x_ref.shape[0]

    @pl.when(v < n)
    def _visit():
        first = (v == 0) | (tiles_ref[jnp.maximum(v - 1, 0)] != tile)
        last = (v == n - 1) | (tiles_ref[v + 1] != tile)

        @pl.when(first & (f == 0))
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, F32)

        x = x_ref[...]
        g = jnp.dot(x, wg_ref[...], preferred_element_type=F32)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=F32)
        h = apply_activation(g, activation) * u
        # the tile's rows of another expert (and past the last) add 0
        e = experts_ref[v]
        row = tile * rows + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0)
        mine = (row >= offsets_ref[e]) & (row < offsets_ref[e + 1])
        h = jnp.where(mine, h, 0.0).astype(x.dtype)
        acc_ref[...] += jnp.dot(h, wd_ref[...], preferred_element_type=F32)

        @pl.when(last & (f == pl.num_programs(1) - 1))
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


# jitted on its own, as kernels/ssm_scan.py's: a model's layers make
# the same call with their own weights, traced and lowered once
@functools.partial(jax.jit, static_argnames=("activation", "row_tile",
                                             "f_tile", "interpret"))
def _ffn_pallas(rows, counts, wg, wu, wd, *, activation, row_tile, f_tile,
                interpret):
    s, d = rows.shape
    e, _, f = wg.shape
    nf = f // f_tile
    offsets, tiles, experts, n = visit_list(counts, s, row_tile)

    # a dead visit's blocks are its predecessor's: the last live
    # visit's last tile of F
    def ftile(v, j, n):
        return jnp.where(v < n[0], j, nf - 1)

    def x_map(v, j, offsets, tiles, experts, n):
        return tiles[v], 0

    def up_map(v, j, offsets, tiles, experts, n):
        return experts[v], 0, ftile(v, j, n)

    def down_map(v, j, offsets, tiles, experts, n):
        return experts[v], ftile(v, j, n), 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s // row_tile + e - 1, nf),
        in_specs=[
            pl.BlockSpec((row_tile, d), x_map),
            pl.BlockSpec((None, d, f_tile), up_map),              # wg
            pl.BlockSpec((None, d, f_tile), up_map),              # wu
            pl.BlockSpec((None, f_tile, d), down_map),            # wd
        ],
        out_specs=pl.BlockSpec((row_tile, d), x_map),
        scratch_shapes=[pltpu.VMEM((row_tile, d), F32)],
    )
    # the pipelined blocks (x, out, three weight tiles), the
    # accumulator, and g, u, h in f32 with h's bf16 copy
    block_bytes = 2 * (2 * row_tile * d + 3 * d * f_tile) \
        + 4 * row_tile * d + 14 * row_tile * f_tile
    y = pl.pallas_call(
        functools.partial(_ffn_kernel, activation=activation),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, d), rows.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(block_bytes)),
        interpret=interpret,
        name="grouped_ffn",
    )(offsets, tiles, experts, n.reshape(1), rows, wg, wu, wd)
    routed = jnp.arange(s) < offsets[e]
    return jnp.where(routed[:, None], y, jnp.zeros_like(y))


def grouped_ffn(rows, counts, wg, wu, wd, activation, *, row_tile=None,
                f_tile=None, interpret=False):
    """`ops/moe.py::ragged_ffn`: rows (S, D) sorted by expert, counts
    (E,) live rows per expert, wg, wu (E, D, F), wd (E, F, D) ->
    (act(rows wg_e) * (rows wu_e)) wd_e (S, D); rows past sum(counts)
    zero. bf16 operands, f32 accumulation; only `h` is rounded (the
    twin rounds `g`, `u` and `h`)."""
    return _ffn_pallas(
        rows, counts, wg, wu, wd, activation=activation,
        row_tile=int(row_tile or ROW_TILE),
        f_tile=int(f_tile or choose_f_tile(wg.shape[-1])),
        interpret=interpret)
