"""Block-paged KV-cache manager with prefix caching.

The device cache is a fixed pool of PAGES — (page_size, heads, head_dim)
K and V blocks per layer — and each sequence owns a PAGE TABLE mapping
its logical token positions to physical pages, exactly the layout of
"Ragged Paged Attention" serving kernels (PAPERS.md): token t of a
sequence lives at page `table[t // page_size]`, offset `t % page_size`.

Why pages instead of one (max_seqs, max_len) rectangle: a rectangle
reserves max_len tokens of HBM per slot whether or not the sequence uses
them; pages let short and long sequences share one pool, so capacity is
bounded by TOTAL resident tokens, not max_seqs * max_len. Freeing a
finished sequence returns whole pages to the pool — reuse is
defrag-free because pages are fixed-size and position-independent.

Three properties layered on top of the PR 1 allocator:

  * Per-page REFCOUNTS: a page can be mapped by several slots at once.
    The K/V of a token block depends only on the token content and its
    position, so two sequences with the same prompt prefix can read the
    same physical pages. A page returns to circulation only when its
    refcount hits 0.
  * PREFIX HASHING: every COMPLETED page (all page_size positions
    written with real K/V) can be registered under a chain hash of its
    token content — key_i = H(key_{i-1} || tokens[i*ps:(i+1)*ps]) — so
    `match_prefix` finds the longest resident run of pages for a new
    prompt in O(pages). Partial (tail) pages are never shared: they are
    still being written by their owner. A hashed page whose refcount
    drops to 0 is NOT freed — it parks in an LRU of reclaimable cached
    pages, still matchable, and is evicted (hash dropped) only when the
    allocator runs dry. `free_pages` therefore counts reclaimable
    capacity: truly-free pages plus the evictable LRU.
  * ON-DEMAND ALLOCATION: slots claim pages as their sequence actually
    grows (`ensure_capacity` / `append_token` allocate when a page
    boundary is crossed) instead of reserving prompt+max_new up front.
    Effective batch size is bounded by actual residency; the scheduler
    pairs this with a preemption path for the rare pool-exhausted step.

Page 0 is reserved as the write SINK: padding lanes of the static-shape
steps scatter their K/V there through page-table entries of 0, so the
jitted steps never need a masked scatter. Reads are masked by sequence
length, so sink contents are never observed.

THREE KINDS OF PER-SEQUENCE STATE (a model whose description holds a
`HybridSpec`: serve/arch.Phi4Flash). Pages, as above, for the layers
the model pages (`KVCacheConfig.num_layers`: there, one). Besides them
a sequence's SLOT holds a constant part that needs no allocator:

  * a RING of the window layers' keys: `ring_pages` pages a slot in a
    pool of their own, logical page p of slot s at physical page
    1 + s * ring_pages + p % ring_pages. A window layer reads only the
    last `window` positions, and one step writes at most a chunk of
    `chunk` tokens before it reads, so window + chunk - 1 consecutive
    positions (at most ring_pages pages) are ever live at once: what
    falls behind is overwritten, never held. The ring's page table is
    a function of the slot and nothing else (`ring_tables`).
  * a scan STATE (f32) and a convolution TAIL for each state-space
    layer, row s of two slabs whose last row is the write sink of the
    step's inactive lanes. A sequence (re-)admitted at position 0
    reads neither (the step starts it from zeros).
  * a TAIL WITHOUT A STATE for each layer whose whole cache is its
    convolution's last inputs (LFM2's gated short convolution: 2 rows
    of the hidden size, 8 KB a layer a sequence whatever its length):
    row s of the `tail` slab alone — no state slab, no page.

Admission prices the constant part by the slot it takes: it exists for
every slot from the start (`KVCacheConfig.constant_bytes_per_seq`,
counted in `pool_bytes`). `HybridPool` is the device half of all three.

Host/device split: `PagedKVCache` owns only HOST bookkeeping (free
list, refcounts, hash registry, page tables, lengths) as plain
numpy/dicts the scheduler mutates freely — the manager never touches
device memory. The device arrays are a `KVPool`: the one type that
knows their layout. It is created once (`KVPool.alloc`) and flows
functionally through the engine's jitted programs (donated in,
returned out).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from ..config import KV_DTYPES  # the ONE --kv-dtype allowlist
from ..kernels.paged_ragged_v2 import quantize_kv_rows

# KV_DTYPES names that store quantized values against per-row scale
# arrays (the PR 8 scale machinery; fp8 reuses it with no new
# bookkeeping — only the page dtype and the qmax change).
QUANTIZED_KV_DTYPES = ("int8", "float8_e4m3")

# --kv-dtype name -> the dtype actually stored in the page arrays.
# "float8_e4m3" stores ml_dtypes' float8_e4m3fn (the finite-only OCP
# variant every jax build ships; the no-suffix e4m3 is newer and not
# universally available).
_KV_STORAGE_ALIASES = {"float8_e4m3": "float8_e4m3fn"}


def kv_storage_dtype(name: str):
    """numpy/jnp dtype of the page arrays for a --kv-dtype name."""
    import jax.numpy as jnp
    return jnp.dtype(_KV_STORAGE_ALIASES.get(str(name), str(name)))


def prefix_page_keys(tokens: Sequence[int], page_size: int,
                     num_pages: int, *, start: int = 0,
                     prev: bytes = b"") -> List[bytes]:
    """Chain hashes for FULL pages [start, num_pages) of `tokens`:
    key_i = sha256(key_{i-1} || block_i_bytes). Position-dependence is
    implicit in the chain (block i's key commits to every token before
    it), so equal keys mean equal (content, position) — the sharing
    precondition. Callers extending an existing chain pass `start` and
    the last known key as `prev`, so per-sequence hashing stays O(pages)
    instead of O(pages^2) across incremental extensions."""
    keys: List[bytes] = []
    for i in range(start, num_pages):
        block = np.asarray(tokens[i * page_size:(i + 1) * page_size],
                           dtype=np.int32)
        prev = hashlib.sha256(prev + block.tobytes()).digest()
        keys.append(prev)
    return keys


@dataclasses.dataclass(frozen=True)
class HybridSpec:
    """What a sequence holds besides pages (module docstring): the
    window layers' ring and the state-space layers' state and tail
    (`state_layers` 0: rings alone, and nothing is allocated or
    computed for state; `window_layers` 0: states alone, and no ring; a
    `tail_shape` of no rows: a state with no tail, as a linear-attention
    layer's matrix state is). A TAIL WITHOUT A STATE: `tail_layers`
    layers hold `tail_shape` a sequence and nothing else (a short
    convolution's last inputs) — the `tail` slab has that many layers
    and there is no state slab; a model holds tails beside its states
    (`state_layers`) or alone (`tail_layers`), never both. `chunk` is
    the most tokens of ONE sequence a step writes (the engine's prefill
    budget)."""
    window_layers: int
    window: int
    chunk: int
    state_layers: int = 0
    state_shape: Tuple[int, int] = (0, 0)   # (d_state, d_inner), f32
    tail_shape: Tuple[int, int] = (0, 0)    # (d_conv - 1, d_inner)
    tail_dtype: str = "bfloat16"
    tail_layers: int = 0                    # tails that ride no state

    def __post_init__(self):
        if self.tail_layers and (self.state_layers
                                 or not self.tail_bytes):
            raise ValueError(
                f"tail_layers={self.tail_layers} are tails WITHOUT a state "
                f"(state_layers={self.state_layers}) of a shape with rows "
                f"(tail_shape={self.tail_shape})")

    @property
    def tail_bytes(self) -> int:
        """One sequence's tail of one layer."""
        return self.tail_shape[0] * self.tail_shape[1] \
            * jnp.dtype(self.tail_dtype).itemsize

    @property
    def tails(self) -> int:
        """The `tail` slab's layers: one beside each state, or the
        tails that ride none; 0: no layer holds a tail."""
        if not self.tail_bytes:
            return 0
        return self.state_layers or self.tail_layers

    def ring_pages(self, page_size: int) -> int:
        """Pages that cover any window + chunk - 1 consecutive
        positions: one more than their whole pages (none without a
        window layer)."""
        if not self.window_layers:
            return 0
        return -(-(self.window + self.chunk - 2) // page_size) + 1

    @property
    def state_bytes(self) -> int:
        """One sequence's scan states and tails, all layers (a model
        whose slots are tails alone: its tails)."""
        return self.state_layers * self.state_shape[0] \
            * self.state_shape[1] * 4 + self.tails * self.tail_bytes


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    """Geometry of the paged pool. Built from FFConfig + model shape via
    :meth:`from_ff` so every serving component sizes itself from the
    same knobs (config.py kv_page_size / kv_num_pages / kv_dtype /
    kv_pool_mb / serve_max_seqs).

    ``kv_dtype`` selects the PAGE STORAGE format: float32 (exact),
    bfloat16 (values round on write; exact when the engine's activation
    dtype is already bf16), or int8 (quantized with per-page scale
    arrays — one f32 scale per head per in-page token slot, see
    `KVPool`). Scales are per-slot rather than per-whole-page
    because pages fill INCREMENTALLY (decode appends one token at a
    time): a page-global amax would have to re-quantize every resident
    token whenever a new token raised it, which is neither cheap nor
    rollback-safe, while per-slot scales keep quantization write-local
    so chunk boundaries, preemption replays, and speculative rollbacks
    cannot change what any resident token dequantizes to.

    All BYTE accounting (``page_bytes``, ``pool_bytes``, the
    ``kv_pool_mb`` sizing below) derives from the configured dtype's
    itemsize — never a hardcoded 4 — so watermark fractions, ladder
    rung thresholds and ``ensure_capacity`` (all page-COUNT math over
    ``usable_pages``) automatically see the larger effective pool a
    quantized format buys at the same byte budget.

    ``tensor_parallel`` is the serve mesh's tensor degree (docs/
    serving.md "Sharded serving"): pages shard on the HEAD axis, so
    every device holds all ``num_pages`` pages at ``num_heads / t``
    heads each. The page COUNT — and with it every watermark /
    degradation-ladder / ``ensure_capacity`` fraction — is therefore
    per-device-identical, while the per-device BYTES drop t×
    (``page_device_bytes``). ``kv_pool_mb`` is a PER-DEVICE HBM budget
    (the physically meaningful knob): sizing divides it by
    ``page_device_bytes``, so a sharded pool holds ~t× the pages at
    the same per-chip budget and the ladder rungs fire at the same
    relative per-device pressure. All host-side page / refcount /
    prefix bookkeeping stays replicated and tp-agnostic."""

    num_layers: int
    num_heads: int
    head_dim: int
    page_size: int = 16
    num_pages: int = 257  # including the reserved sink page 0
    max_seqs: int = 8
    max_seq_len: int = 512  # logical cap; rounds up to whole pages
    kv_dtype: str = "float32"
    tensor_parallel: int = 1  # head-sharding degree of the serve mesh
    hybrid: Optional[HybridSpec] = None  # the slots' constant part
    # pages stored head-PACKED, (page, slot, heads * head_dim): KVPool
    packed_heads: bool = False
    # width of the SELECTOR's row a page a pool layer (KVPool.kc: the
    # compressed key a page's last token completes); 0: none
    selector_dim: int = 0
    # each key/value head's pages a POOL LAYER of their own (`layer *
    # num_heads + head`, one head a page row; `head_layers`): what a
    # pool with selector rows chooses, because every head then selects
    # its own blocks and a block's pages are whole rows (sparse_paged.py)
    split_heads: bool = False

    @classmethod
    def from_ff(cls, config, *, num_layers: int, num_heads: int,
                head_dim: int, max_seq_len: int = 512,
                tensor_parallel: int = 1,
                hybrid: Optional[HybridSpec] = None,
                selector_dim: int = 0) -> "KVCacheConfig":
        kv_dtype = str(getattr(config, "kv_dtype", "float32"))
        num_pages = int(getattr(config, "kv_num_pages", 257))
        pool_mb = float(getattr(config, "kv_pool_mb", 0.0) or 0.0)
        tp = max(1, int(tensor_parallel))
        if pool_mb > 0:
            # byte-budget sizing: the page count FOLLOWS the storage
            # format (the quantized-capacity lever — int8 pages cost
            # ~1/4 the bytes, so the same budget holds ~4x the pages)
            # AND the sharding degree: the budget is per-DEVICE HBM,
            # and a head-sharded page costs 1/t of its bytes on each
            # device, so the same per-chip budget holds ~t× the pages
            # — which is exactly what keeps every page-count-fraction
            # threshold (watermark, ladder rungs) firing at the same
            # relative per-device pressure under sharding.
            probe = cls(num_layers=num_layers, num_heads=num_heads,
                        head_dim=head_dim,
                        page_size=int(getattr(config, "kv_page_size", 16)),
                        num_pages=2, max_seqs=1,
                        max_seq_len=max_seq_len, kv_dtype=kv_dtype,
                        tensor_parallel=tp)
            num_pages = 1 + max(1, int(pool_mb * (1 << 20))
                                // probe.page_device_bytes)
        return cls(num_layers=num_layers, num_heads=num_heads,
                   head_dim=head_dim,
                   page_size=int(getattr(config, "kv_page_size", 16)),
                   num_pages=num_pages,
                   max_seqs=int(getattr(config, "serve_max_seqs", 8)),
                   max_seq_len=max_seq_len, kv_dtype=kv_dtype,
                   tensor_parallel=tp, hybrid=hybrid,
                   packed_heads=hybrid is not None,
                   selector_dim=int(selector_dim),
                   split_heads=selector_dim > 0)

    @property
    def pages_per_seq(self) -> int:
        """Static page-table width (logical max_seq_len in pages)."""
        return -(-self.max_seq_len // self.page_size)

    @property
    def usable_pages(self) -> int:
        return self.num_pages - 1  # minus the sink

    # ---------------- storage format / byte accounting ----------------
    @property
    def quantized(self) -> bool:
        return self.kv_dtype in QUANTIZED_KV_DTYPES

    @property
    def storage_dtype(self):
        """The dtype actually stored in the page arrays (resolves the
        float8_e4m3 -> float8_e4m3fn alias)."""
        return kv_storage_dtype(self.kv_dtype)

    @property
    def kv_itemsize(self) -> int:
        return int(self.storage_dtype.itemsize)

    @property
    def page_bytes(self) -> int:
        """Device bytes ONE page costs across all layers: K + V values
        at kv_dtype itemsize, plus the f32 scale rows when quantized.
        The basis for every byte-level pool computation (never assume
        4 bytes/element)."""
        values = (2 * self.num_layers * self.page_size * self.num_heads
                  * self.head_dim * self.kv_itemsize)
        scales = (2 * self.num_layers * self.page_size * self.num_heads
                  * 4) if self.quantized else 0
        return values + scales + self.selector_page_bytes

    @property
    def pool_layers(self) -> int:
        """Layers of the pool's arrays."""
        return self.num_layers * (self.num_heads if self.split_heads else 1)

    @property
    def layer_heads(self) -> int:
        """Key/value heads in one pool layer: what ONE paged call reads."""
        return 1 if self.split_heads else self.num_heads

    def head_layers(self, layer: int) -> list:
        """The pool layers that hold paged layer `layer`'s heads."""
        each = self.pool_layers // self.num_layers
        return list(range(layer * each, (layer + 1) * each))

    @property
    def selector_dtype(self):
        """The compressed keys' dtype: the pages' own where that is a
        float format the selector can score in, bfloat16 on a quantized
        pool (a mean of dequantized keys has no scale row to live by)."""
        return jnp.dtype(jnp.bfloat16) if self.quantized \
            else self.storage_dtype

    @property
    def selector_page_bytes(self) -> int:
        """One page's selector rows across the pool's layers."""
        return (self.pool_layers * self.selector_dim
                * int(self.selector_dtype.itemsize))

    @property
    def f32_page_bytes(self) -> int:
        """What the same page geometry costs in float32 pages — the
        baseline for the quantized-capacity comparison."""
        return (2 * self.num_layers * self.page_size * self.num_heads
                * self.head_dim * 4)

    @property
    def pool_bytes(self) -> int:
        """Everything the cache holds on the device: the pages, and
        every slot's constant part (the rings' sink page with them)."""
        return self.num_pages * self.page_bytes + self.constant_bytes

    # ---------------- the slots' constant part (HybridSpec) -----------
    @property
    def ring_pages(self) -> int:
        return self.hybrid.ring_pages(self.page_size) if self.hybrid else 0

    @property
    def ring_page_bytes(self) -> int:
        """One ring page across the window layers."""
        if not self.hybrid:
            return 0
        return (self.page_bytes - self.selector_page_bytes) \
            // self.num_layers * self.hybrid.window_layers

    @property
    def cache_bytes_per_token(self) -> int:
        """What one more token of context costs a sequence."""
        return self.page_bytes // self.page_size

    @property
    def constant_bytes_per_seq(self) -> int:
        """What a sequence holds whatever its length: its ring and its
        states (0 for a model of pages alone)."""
        if not self.hybrid:
            return 0
        return self.ring_pages * self.ring_page_bytes \
            + self.hybrid.state_bytes

    @property
    def constant_bytes(self) -> int:
        """All slots' constant parts, with the rings' sink page and the
        slabs' sink row."""
        if not self.hybrid:
            return 0
        return self.max_seqs * self.constant_bytes_per_seq \
            + self.ring_page_bytes + self.hybrid.state_bytes

    # ---------------- per-device accounting (sharded serving) ---------
    @property
    def heads_per_device(self) -> int:
        return self.num_heads // max(1, self.tensor_parallel)

    @property
    def page_device_bytes(self) -> int:
        """Device bytes ONE page costs under head sharding: both the
        value blocks and the scale rows carry the head axis, so the
        whole page cost divides exactly by the tensor degree."""
        return self.page_bytes // max(1, self.tensor_parallel)

    @property
    def pool_device_bytes(self) -> int:
        return self.num_pages * self.page_device_bytes \
            + self.constant_bytes

    @property
    def effective_page_ratio(self) -> float:
        """Pages this format fits per byte, relative to f32 — the
        capacity multiplier int8 buys at an equal pool budget."""
        return self.f32_page_bytes / self.page_bytes

    def validate(self) -> None:
        if self.page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {self.page_size}")
        if self.num_pages < 2:
            raise ValueError(
                f"num_pages must be >= 2 (page 0 is the reserved sink), "
                f"got {self.num_pages}")
        if self.kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {KV_DTYPES}, got "
                f"{self.kv_dtype!r}")
        if self.pages_per_seq > self.usable_pages:
            raise ValueError(
                f"one max-length sequence needs {self.pages_per_seq} pages "
                f"but the pool only has {self.usable_pages} usable")
        if self.tensor_parallel < 1:
            raise ValueError(
                f"tensor_parallel must be >= 1, got "
                f"{self.tensor_parallel}")
        if self.num_heads % max(1, self.tensor_parallel) != 0:
            raise ValueError(
                f"head-sharded serving needs num_heads "
                f"({self.num_heads}) divisible by the tensor degree "
                f"({self.tensor_parallel})")
        if self.hybrid and self.tensor_parallel > 1:
            raise ValueError("a slot's ring and states are not sharded")


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["k", "v", "k_scale", "v_scale", "kc"],
    meta_fields=["heads"])
@dataclasses.dataclass(frozen=True)
class KVPool:
    """The device K/V pool, and the ONE place that knows its format.

    Layout: `k`, `v` are (layer, page, slot, head, dim) at the
    configured storage dtype; on quantized (int8 / fp8) pools
    `k_scale`, `v_scale` are (layer, page, slot, head) f32 — one scale
    per stored row (see `KVCacheConfig` for why per row) — and None
    otherwise. A registered pytree whose leaves flatten in that order,
    so a jitted program takes and returns a pool (donated) and the
    pytree's structure, not an argument, says whether it is quantized.
    The engine, the handoff and the tests go through the methods below;
    nothing else indexes, scatters into or shards a leaf.

    PACKED layout (`heads` > 0; `KVCacheConfig.packed_heads`): `k`, `v`
    are (layer, page, slot, head * dim), the rows as the paged kernel
    streams them. A head count that is no multiple of the TPU's 8 (16
    in bf16) sublanes pads every (head, dim) tile of the unpacked
    layout — 10 heads of 128 to 16 — and XLA then copies the whole
    pool between its padded and a compact layout around every layer;
    packed, a page is (slot, 1280): whole tiles. The paged kernel
    reads a packed pool where it lies (`layer` hands it the whole
    leaves and the layer's first row); of an unpacked pool it is handed
    a layer's slice, which XLA copies out for every call.

    SELECTOR rows (`kc`; `KVCacheConfig.selector_dim` > 0, else None and
    no leaf): (layer, page, selector_dim), one row a page — the
    compressed key of a block-sparse attention layer's selector
    (ops/sparse_attention.py) that the page's LAST token completed,
    written by `write_selector` after `write` has stored that token's
    key. A pool without them flattens to the leaves it always had."""
    k: Any
    v: Any
    k_scale: Any = None
    v_scale: Any = None
    kc: Any = None
    heads: int = 0

    @classmethod
    def alloc(cls, cfg: KVCacheConfig, sharding=None) -> "KVPool":
        """The zeroed pool of `cfg`'s geometry. `sharding`: None (the
        default device), one Sharding for every leaf (a placed one-chip
        replica), or a KVPool of them (`specs` over a serve mesh: each
        device then holds its H/t heads of every page). Allocated IN
        the sharding: a whole pool zero-filled on the default chip and
        then resharded would need the unsharded bytes there first."""
        sh = sharding if isinstance(sharding, cls) \
            else cls(sharding, sharding, sharding, sharding)
        rows = (cfg.pool_layers, cfg.num_pages, cfg.page_size,
                cfg.layer_heads)
        dt = cfg.storage_dtype
        page = rows[:3] + (cfg.layer_heads * cfg.head_dim,) \
            if cfg.packed_heads else rows + (cfg.head_dim,)
        pool = cls(jnp.zeros(page, dt, device=sh.k),
                   jnp.zeros(page, dt, device=sh.v),
                   heads=cfg.layer_heads if cfg.packed_heads else 0)
        if cfg.selector_dim:
            pool = dataclasses.replace(pool, kc=jnp.zeros(
                rows[:2] + (cfg.selector_dim,), cfg.selector_dtype,
                device=sh.k))
        if not cfg.quantized:
            return pool
        return dataclasses.replace(
            pool,
            k_scale=jnp.zeros(rows, jnp.float32, device=sh.k_scale),
            v_scale=jnp.zeros(rows, jnp.float32, device=sh.v_scale))

    @staticmethod
    def specs(axis: str) -> "KVPool":
        """The leaves' PartitionSpecs with the head axis on mesh axis
        `axis` (a spec of a leaf the pool lacks matches its None)."""
        page = PartitionSpec(None, None, None, axis, None)
        scale = PartitionSpec(None, None, None, axis)
        return KVPool(page, page, scale, scale)

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    def write(self, layer: int, pages, offs, k, v) -> "KVPool":
        """Store rows k, v (T, H, D) of `layer` at (pages[t], offs[t]):
        lossless pools cast on the scatter (f32 pages keep activation
        values exactly, bf16 pages round); quantized pools quantize
        each (token, head) row against its own amax scale and store the
        scale beside it."""
        row = (lambda a: a.reshape(a.shape[0], -1)) if self.heads \
            else (lambda a: a)
        if not self.quantized:
            return dataclasses.replace(
                self,
                k=self.k.at[layer, pages, offs].set(
                    row(k.astype(self.k.dtype))),
                v=self.v.at[layer, pages, offs].set(
                    row(v.astype(self.v.dtype))))
        kq, ksc = quantize_kv_rows(k, self.k.dtype)
        vq, vsc = quantize_kv_rows(v, self.v.dtype)
        return dataclasses.replace(
            self, k=self.k.at[layer, pages, offs].set(row(kq)),
            v=self.v.at[layer, pages, offs].set(row(vq)),
            k_scale=self.k_scale.at[layer, pages, offs].set(ksc),
            v_scale=self.v_scale.at[layer, pages, offs].set(vsc))

    def write_selector(self, layer: int, pages, rows) -> "KVPool":
        """Store the selector's rows (T, selector_dim) of `layer` at
        pages[t] (a lane that completes none aims at the sink page)."""
        return dataclasses.replace(
            self, kc=self.kc.at[layer, pages].set(
                rows.astype(self.kc.dtype)))

    def gather(self, layer: int, pages):
        """K and V of whole pages `pages` (any shape, int32) of `layer`
        -> (k, v), each pages.shape + (slot, heads * dim): the rows as
        stored on a lossless pool, dequantized to f32 on a quantized
        one. A row gather over the WHOLE pool, pages as rows (layer *
        num_pages + page): a slice of one layer handed to a gather is
        copied out first, 0.25 GiB at a served size. Packed pools."""
        if self.heads == 0:
            raise ValueError("gather reads a head-packed pool")
        n_pages = self.k.shape[1]
        rows = layer * n_pages + jnp.asarray(pages, jnp.int32)

        def take(a):
            return jnp.take(a.reshape((-1,) + a.shape[2:]), rows, axis=0,
                            mode="clip")

        k, v = take(self.k), take(self.v)
        if not self.quantized:
            return k, v
        each = self.k.shape[-1] // self.heads

        def scaled(q, scale):
            return q.astype(jnp.float32) * jnp.repeat(
                take(scale), each, axis=-1)

        return scaled(k, self.k_scale), scaled(v, self.v_scale)

    def selector_table(self):
        """The selector's rows of all the layers as ONE table (layer *
        page, selector_dim), what `selector_rows` gathers from. Merging
        the leading dimensions lays the leaf out anew where a layer's
        pages are no whole tiles, so a program that gathers in several
        places (or in a loop's body) makes the table once, before
        them."""
        return self.kc.reshape(-1, self.kc.shape[-1])

    def selector_rows(self, table, layer: int, pages):
        """The selector's rows of `pages` (any shape) of `layer` from
        `selector_table()`, pages.shape + (selector_dim,), gathered
        like `gather`."""
        rows = layer * self.kc.shape[1] + jnp.asarray(pages, jnp.int32)
        return jnp.take(table, rows, axis=0, mode="clip")

    def layer(self, i: int):
        """Layer `i`'s operands of the paged attention kernel
        (kernels/paged_ragged_v2.paged_attention_ragged_v2): (k_pages,
        v_pages, k_scales, v_scales, page_base), the scales None on a
        lossless pool.

        A PACKED pool is read IN PLACE: its leaves are stored as the
        rows the kernel streams, so the operands are the whole leaves
        as rows of all the layers — (layer * page, slot, head * dim),
        the scales (layer * page, slot, head); merging the leading
        dimensions is a view — and `page_base` = i * num_pages, the row
        of the layer's page 0, which the kernel adds to every page it
        fetches. An UNPACKED pool hands the layer's slice, pages (page,
        slot, head, dim), the scales (page, slot, head), and no base:
        the kernel packs the heads of that slice, and a Mosaic call
        needs a whole buffer, so XLA copies the slab out of the pool
        and lays it out anew for every call (ROADMAP S2)."""
        if not self.heads:
            scales = (self.k_scale[i], self.v_scale[i]) if self.quantized \
                else (None, None)
            return (self.k[i], self.v[i]) + scales + (None,)
        k, v, ks, vs = (
            None if a is None else a.reshape((-1,) + a.shape[2:])
            for a in (self.k, self.v, self.k_scale, self.v_scale))
        return k, v, ks, vs, jnp.int32(i * self.k.shape[1])

    def rows(self, idx) -> "KVPool":
        """Whole pages `idx` of every layer, as a pool of len(idx)
        pages — the handoff's gather (the wire carries its leaves)."""
        return jax.tree.map(lambda a: a[:, idx], self)

    def with_rows(self, idx, rows: "KVPool") -> "KVPool":
        """The pool with `rows` (what `rows(idx)` gave, here or on
        another engine) stored at pages `idx`."""
        return jax.tree.map(lambda a, r: a.at[:, idx].set(r), self, rows)

    def check_geometry(self, cfg: KVCacheConfig) -> None:
        """The leaves are what `alloc(cfg)` makes: a drifted shape or
        dtype would dequantize every resident token against the wrong
        scale rows."""
        want = jax.eval_shape(lambda: KVPool.alloc(cfg))
        assert self.quantized == want.quantized, (
            f"kv_dtype={cfg.kv_dtype} pool "
            f"{'carries' if self.quantized else 'lacks'} scale arrays")
        assert (self.kc is None) == (want.kc is None), (
            f"pool {'carries' if self.kc is not None else 'lacks'} "
            f"selector rows; selector_dim is {cfg.selector_dim}")
        names = [n for n in ("k", "v", "k_scale", "v_scale", "kc")
                 if getattr(self, n) is not None]
        for name, a, w in zip(names, jax.tree.leaves(self),
                              jax.tree.leaves(want)):
            assert (a.shape, a.dtype) == (w.shape, w.dtype), (
                f"pool leaf {name} is {a.shape} {a.dtype}; the "
                f"configuration's is {w.shape} {w.dtype}")

    def check_scales(self, where: Sequence[Tuple[str, int, int]]) -> None:
        """Audit the stored rows at `where` = (what, page, offset) of a
        quantized pool: K/V scales finite and non-negative, and a zero
        scale vouching for an all-zero stored row (scale 0 is only
        ever written for an all-zero activation row, so anything else
        means a scale and its page drifted apart)."""
        if not self.quantized or not where:
            return
        for name, q, s in (("k", self.k, self.k_scale),
                           ("v", self.v, self.v_scale)):
            q, s = np.asarray(q), np.asarray(s)
            for what, page, off in where:
                srow = s[:, page, off]             # (layers, H)
                qrow = q[:, page, off]             # (layers, H, D)
                assert np.all(np.isfinite(srow)) \
                    and np.all(srow >= 0), (
                    f"{name}-scale of {what} (page {page} off {off}) "
                    f"is not finite/non-negative")
                assert np.all(qrow[srow == 0.0] == 0), (
                    f"{name}-page row of {what} (page {page} off "
                    f"{off}) has zero scale but nonzero quantized "
                    f"content")


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["full", "window", "state", "tail"], meta_fields=[])
@dataclasses.dataclass(frozen=True)
class HybridPool:
    """The device half of a HybridSpec configuration: `full` the pages
    of the paged layers (a KVPool of `cfg.num_layers` layers), `window`
    the slots' rings (a KVPool of the window layers, 1 + max_seqs *
    ring_pages pages: the sink, then slot s's ring; None without a
    window layer), `state`
    (state_layers, max_seqs + 1, d_state, d_inner) f32 and `tail`
    (state_layers, max_seqs + 1, (d_conv - 1) * d_inner) (its rows
    flat: three rows would pad to a tile of 16), the last row of both
    the write sink; both None without a state-space layer, `tail` also
    where the state has none (no zero-sized leaf in the donated pool).
    A model whose slots are TAILS ALONE (HybridSpec.tail_layers) has
    `tail` (tail_layers, max_seqs + 1, rows * width) and `state` None:
    no stand-in state of one row or none.
    Flows through the step like a KVPool (donated in, returned out)."""
    full: KVPool
    window: KVPool
    state: Any
    tail: Any

    @staticmethod
    def ring_cfg(cfg: KVCacheConfig) -> KVCacheConfig:
        """The rings as a page pool's geometry."""
        return dataclasses.replace(
            cfg, num_layers=cfg.hybrid.window_layers,
            num_pages=1 + cfg.max_seqs * cfg.ring_pages, hybrid=None,
            selector_dim=0, split_heads=False)

    @classmethod
    def alloc(cls, cfg: KVCacheConfig, sharding=None) -> "HybridPool":
        h = cfg.hybrid
        rows = (h.state_layers, cfg.max_seqs + 1)
        full = KVPool.alloc(dataclasses.replace(cfg, hybrid=None), sharding)
        window = KVPool.alloc(cls.ring_cfg(cfg), sharding) \
            if h.window_layers else None
        tail = h.tail_shape[0] * h.tail_shape[1]
        return cls(
            full, window,
            jnp.zeros(rows + tuple(h.state_shape), jnp.float32,
                      device=sharding) if h.state_layers else None,
            jnp.zeros((h.tails, cfg.max_seqs + 1, tail),
                      jnp.dtype(h.tail_dtype), device=sharding)
            if h.tails else None)

    def check_geometry(self, cfg: KVCacheConfig) -> None:
        want = jax.eval_shape(lambda: HybridPool.alloc(cfg))
        self.full.check_geometry(dataclasses.replace(cfg, hybrid=None))
        if want.window is None:
            assert self.window is None, "rings without a window layer"
        else:
            self.window.check_geometry(self.ring_cfg(cfg))
        for name in ("state", "tail"):
            a, w = getattr(self, name), getattr(want, name)
            if w is None:
                assert a is None, (f"pool leaf {name} that no layer of "
                                   f"the configuration holds")
                continue
            assert (a.shape, a.dtype) == (w.shape, w.dtype), (
                f"pool leaf {name} is {a.shape} {a.dtype}; the "
                f"configuration's is {w.shape} {w.dtype}")


def ring_tables(cfg: KVCacheConfig, xp=np):
    """(max_seqs, pages_per_seq) int32: the rings' page table, the same
    for ever (numpy on the host, jax.numpy inside the step)."""
    r = cfg.ring_pages
    slot = xp.arange(cfg.max_seqs, dtype=xp.int32)[:, None]
    page = xp.arange(cfg.pages_per_seq, dtype=xp.int32)[None, :]
    return (1 + slot * r + page % r).astype(xp.int32)


class PagedKVCache:
    """Host-side page allocator + per-slot page tables + prefix cache.

    Slots are the static decode-batch lanes (0..max_seqs-1); the
    scheduler binds a running request to a slot and this class binds the
    slot to pages. All arrays are padded to static shapes so the jitted
    steps see one geometry forever:

      page_tables  (max_seqs, pages_per_seq) int32, 0 = sink/unmapped
      seq_lens     (max_seqs,) int32, 0 = slot empty

    Every usable page is in exactly one of three states:
      free    — unhashed, in `_free` (LIFO: warmest reuse first)
      cached  — hashed, refcount 0, in the `_lru` (matchable, evictable)
      mapped  — refcount > 0 (referenced by >= 1 slot's table)
    """

    def __init__(self, cfg: KVCacheConfig, prefix_cache: bool = True):
        cfg.validate()
        self.cfg = cfg
        self.prefix_enabled = bool(prefix_cache)
        self._free: List[int] = list(range(cfg.num_pages - 1, 0, -1))
        self._lru: "OrderedDict[int, None]" = OrderedDict()
        self._ref = np.zeros((cfg.num_pages,), dtype=np.int64)
        self._hash_of_page: Dict[int, bytes] = {}
        self._page_of_hash: Dict[bytes, int] = {}
        self.page_tables = np.zeros((cfg.max_seqs, cfg.pages_per_seq),
                                    dtype=np.int32)
        self.seq_lens = np.zeros((cfg.max_seqs,), dtype=np.int32)
        self._slot_free = list(range(cfg.max_seqs - 1, -1, -1))
        # pages whose content arrived over the disaggregated handoff
        # (import_pages) rather than from this engine's own compute:
        # they must stay hashed for as long as they are resident — an
        # imported page the registry stopped vouching for would be
        # unreachable garbage (check_invariants)
        self._imported: set = set()
        # hierarchical prefix cache (serve/host_tier.HostPageStore):
        # when armed, eviction queues (page, key) here instead of
        # silently dropping the identity; the ENGINE drains the queue —
        # DMAing the still-resident device rows into the store — before
        # every dispatch that could overwrite pages (the device pools
        # only mutate through jitted dispatches, so a queued page's
        # content stays valid exactly until then)
        self.host_tier = None
        self._pending_spills: List[Tuple[int, bytes]] = []
        # serving metrics, merged into ServeEngine.last_stats
        self.stats = {"prefix_hit_pages": 0, "prefix_evictions": 0,
                      "pages_committed": 0, "shared_attaches": 0,
                      "max_page_refs": 0, "rollback_pages": 0,
                      "lru_shed_pages": 0, "slots_reclaimed": 0,
                      "exported_pages": 0, "imported_pages": 0,
                      "import_dedup_pages": 0}

    # ---------------- capacity queries (scheduler admission) ----------
    @property
    def free_pages(self) -> int:
        """RECLAIMABLE pages: truly free plus cached-but-unreferenced
        (the LRU is evicted on demand by allocation)."""
        return len(self._free) + len(self._lru)

    @property
    def free_slots(self) -> int:
        return len(self._slot_free)

    def pages_for(self, tokens: int) -> int:
        return -(-tokens // self.cfg.page_size)

    def mapped_pages(self, slot: int) -> int:
        return int(np.count_nonzero(self.page_tables[slot]))

    def mapped_tokens(self, slot: int) -> int:
        """Token capacity already backed by this slot's pages."""
        return self.mapped_pages(slot) * self.cfg.page_size

    def ref(self, page: int) -> int:
        return int(self._ref[page])

    def debug_state(self) -> dict:
        """Bounded JSON-ready pool snapshot for the failure flight
        recorder (docs/observability.md): page-state partition (free /
        parked / mapped), slot residency, refcount spread, and the
        lifetime stats — the numbers a post-mortem needs to answer
        "was the pool wedged" without shipping the page tables."""
        c = self.cfg
        mapped = int(np.count_nonzero(self._ref))
        return {
            "usable_pages": c.usable_pages,
            "free_pages": len(self._free),
            "parked_pages": len(self._lru),
            "mapped_pages": mapped,
            "reclaimable_pages": self.free_pages,
            "occupancy": 1.0 - self.free_pages / c.usable_pages,
            "free_slots": self.free_slots,
            "max_seqs": c.max_seqs,
            "seq_lens": [int(n) for n in self.seq_lens],
            "hashed_pages": len(self._page_of_hash),
            "imported_resident": len(self._imported),
            "max_page_ref": int(self._ref.max()) if mapped else 0,
            "kv_dtype": c.kv_dtype,
            "page_size": c.page_size,
            # the slots' constant part (0 / None for pages alone)
            "ring_pages_per_slot": c.ring_pages,
            "constant_bytes_per_seq": c.constant_bytes_per_seq,
            "hybrid": dataclasses.asdict(c.hybrid) if c.hybrid else None,
            # eviction order (oldest first, bounded): what rung-2 /
            # allocation pressure would shed next — the view rung
            # post-mortems were missing
            "lru_order": [int(p) for p in list(self._lru)[:64]],
            "lru_truncated": max(0, len(self._lru) - 64),
            "pending_spills": len(self._pending_spills),
            "host_tier": (self.host_tier.debug_state()
                          if self.host_tier is not None else None),
            "stats": dict(self.stats),
        }

    # ---------------- prefix cache ------------------------------------
    def match_prefix(self, keys: Sequence[bytes]) -> List[int]:
        """Longest run of resident pages whose chain keys match `keys`
        from the start. Returned pages are NOT reserved — the caller
        must `attach_prefix` them before any allocation can evict the
        refcount-0 ones out of the LRU."""
        pages: List[int] = []
        if not self.prefix_enabled:
            return pages
        for key in keys:
            p = self._page_of_hash.get(key)
            if p is None:
                break
            pages.append(p)
        return pages

    def match_prefix_host(self, keys: Sequence[bytes],
                          resident: int) -> int:
        """The host-tier fall-through of `match_prefix`: how many keys
        BEYOND the `resident` HBM-matched run are held by the armed
        host store (0 when no tier). The pages are NOT reloaded here —
        the scheduler prices DMA-vs-recompute first and only then asks
        the engine to re-import (ServeEngine._host_reload)."""
        if self.host_tier is None or not self.prefix_enabled:
            return 0
        return self.host_tier.match_chain(list(keys[resident:]))

    def touch(self, pages: Sequence[int]) -> None:
        """Refresh parked pages to most-recently-used, so an imminent
        allocation burst (a host-tier reload's import) cannot evict
        the very HBM run an admission just matched."""
        for p in pages:
            p = int(p)
            if p in self._lru:
                self._lru.move_to_end(p)

    def take_pending_spills(self) -> List[Tuple[int, bytes]]:
        """Claim the queued (page, chain key) spill records, clearing
        the queue. The engine calls this immediately before any
        dispatch that writes the device pools and ships each page's
        rows to the host tier — past that point the queued pages may
        be overwritten and the records would vouch for garbage."""
        out, self._pending_spills = self._pending_spills, []
        return out

    def commit_page(self, slot: int, page_idx: int, key: bytes) -> bool:
        """Register a COMPLETED page of `slot` under its content chain
        key, making it matchable by future prompts. No-op when hashing
        is off, the page is already registered, or another page already
        owns the key (first writer wins; deduping the loser is not
        worth a device copy). Returns True when registered."""
        if not self.prefix_enabled:
            return False
        page = int(self.page_tables[slot, page_idx])
        if page == 0:
            raise RuntimeError(
                f"commit_page on unmapped page {page_idx} of slot {slot}")
        if page in self._hash_of_page or key in self._page_of_hash:
            return False
        self._hash_of_page[page] = key
        self._page_of_hash[key] = page
        self.stats["pages_committed"] += 1
        return True

    def _unregister(self, page: int) -> None:
        key = self._hash_of_page.pop(page, None)
        if key is not None:
            del self._page_of_hash[key]
        # a de-hashed imported page is no longer vouched-for handoff
        # content — it is just a free/garbage page again
        self._imported.discard(page)

    def _pop_parked(self, *, spill: bool = True) -> int:
        """Retire the least-recently-parked cached page — the ONE
        eviction primitive `_take_page` and `shrink_lru` share. Split
        into two halves: reclaiming CAPACITY (pop from the LRU) and
        forgetting IDENTITY (unregister the hash) — when `spill` and a
        host tier is armed, the identity is queued as a pending spill
        instead of dropped, so the engine can DMA the page's
        still-resident device rows into the host store before anything
        overwrites them ("spill instead of discard")."""
        page, _ = self._lru.popitem(last=False)
        if spill and self.host_tier is not None:
            key = self._hash_of_page.get(page)
            if key is not None:
                self._pending_spills.append((page, key))
        self._unregister(page)
        return page

    def _take_page(self) -> int:
        """A writable page: the free list first, then evict the
        least-recently-parked cached page (spilling its identity to
        the host tier when one is armed, else dropping its hash)."""
        if self._free:
            return self._free.pop()
        if self._lru:
            page = self._pop_parked()
            self.stats["prefix_evictions"] += 1
            return page
        raise RuntimeError(
            "page pool exhausted (scheduler must check free_pages and "
            "preempt before allocating)")

    def clear_prefix(self) -> int:
        """Drop the ENTIRE prefix registry: every parked LRU page
        returns to the plain free list and every mapped page loses its
        hash. The crash-containment action — after a mid-batch engine
        failure the device arrays the registry's content lived in are
        stale or consumed, so nothing on them may be vouched for.
        Returns the number of hashes dropped."""
        n = len(self._hash_of_page)
        while self._lru:
            page, _ = self._lru.popitem(last=False)
            self._unregister(page)
            self._free.append(page)
        for page in list(self._hash_of_page):
            self._unregister(page)
        # queued spills point at the same stale/consumed device rows —
        # shipping them to the host tier would vouch for garbage
        self._pending_spills.clear()
        return n

    def shrink_lru(self, keep: int, *, spill: bool = True) -> int:
        """Reclaim capacity: evict parked (refcount-0, hashed) pages
        oldest-first until at most `keep` remain, returning them to the
        plain free list. The degradation ladder's rung-2 action: under
        page pressure a parked page is a liability — a prefix attach
        would pin it at refcount > 0 right when admissions need every
        reclaimable page. Whether the IDENTITY is also forgotten is the
        `_pop_parked` split: with a host tier armed (and `spill` left
        on) rung 2 becomes "spill instead of discard" — the key and
        content move down a tier instead of being recomputed from
        tokens later. Returns the number of pages shed."""
        shed = 0
        while len(self._lru) > max(0, int(keep)):
            page = self._pop_parked(spill=spill)
            self._free.append(page)
            shed += 1
        self.stats["lru_shed_pages"] += shed
        return shed

    # ---------------- disaggregated page handoff ----------------------
    # Host-side half of the prefill->decode transfer (serve/disagg.py):
    # export names the FULL, resident pages of a slot with their chain
    # keys; import allocates pages for foreign keys and parks them in
    # the prefix LRU — hashed, refcount 0, matchable — which is
    # EXACTLY the state a locally-computed page reaches when its last
    # owner finishes, so everything downstream (match_prefix /
    # attach_prefix / eviction / the ladder) treats handed-off content
    # identically to local content. The device rows ride separately
    # through ServeEngine.export_kv/import_kv (this class never
    # touches device memory).

    def export_pages(self, slot: int, tokens: Sequence[int], *,
                     prev: bytes = b""
                     ) -> Tuple[List[int], List[bytes], int]:
        """(pages, chain keys, covered tokens) for every FULL page of
        `slot`'s resident sequence — the transfer unit of a
        disaggregated handoff. `tokens` is the slot's context (the
        caller owns it; page content is a pure function of the token
        prefix, which is what makes the chain key a sound transfer
        identity). The partial tail page is never exported: like
        prefix sharing, only whole pages have a content identity —
        the importer recomputes the tail (< page_size tokens), exactly
        as a prefix-cache hit would. `prev` seeds the chain — the
        tenant prefix salt (serve/adapters.tenant_prefix_salt): an
        adapted tenant's pages carry tenant-disjoint keys, so a
        handoff can never alias one tenant's K/V to another's."""
        ps = self.cfg.page_size
        full = int(self.seq_lens[slot]) // ps
        if full * ps > len(tokens):
            raise ValueError(
                f"slot {slot} has {self.seq_lens[slot]} resident "
                f"tokens but only {len(tokens)} were supplied")
        pages = [int(self.page_tables[slot, i]) for i in range(full)]
        if any(p == 0 for p in pages):
            raise RuntimeError(
                f"slot {slot} table is not a mapped prefix over its "
                f"resident length")
        keys = prefix_page_keys(tokens, ps, full, prev=prev)
        self.stats["exported_pages"] += len(pages)
        return pages, keys, full * ps

    def import_pages(self, keys: Sequence[bytes]
                     ) -> List[Tuple[int, int]]:
        """Adopt a handed-off page chain: for every chain key not
        already resident, allocate a page, register the key, and park
        the page in the prefix LRU (refcount 0, hashed, matchable —
        the same state finish-time eviction leaves a local page in).
        Returns [(chain_index, page)] for the pages whose device rows
        the caller must now write (ServeEngine.import_kv); keys that
        are already resident dedupe to nothing — a shared system
        preamble crosses the link ONCE per decode engine, not once per
        request. The caller must have checked `free_pages` against
        len(keys): running the allocator dry here is a cluster
        backpressure bug (DisaggCluster skips the import instead)."""
        if not self.prefix_enabled:
            raise RuntimeError(
                "import_pages needs the prefix cache: an imported page "
                "is only reachable through its chain-key registration")
        out: List[Tuple[int, int]] = []
        for i, key in enumerate(keys):
            if key in self._page_of_hash:
                self.stats["import_dedup_pages"] += 1
                continue
            page = self._take_page()
            self._hash_of_page[page] = key
            self._page_of_hash[key] = page
            self._lru[page] = None     # most-recently parked
            self._imported.add(page)
            out.append((i, page))
        self.stats["imported_pages"] += len(out)
        return out

    def imported_pages(self) -> Tuple[int, ...]:
        """Pages whose resident content arrived over the handoff link
        (still hashed — eviction drops them from this set too)."""
        return tuple(sorted(self._imported))

    def key_resident(self, key: bytes) -> bool:
        """Whether a chain key is already registered here — what the
        cluster's backpressure check counts a shipment's NEW pages
        with (resident keys dedupe on import)."""
        return key in self._page_of_hash

    # ---------------- slot lifecycle ----------------------------------
    def release_all(self) -> int:
        """Free every occupied slot (crash recovery: a serving loop
        died between allocation and the bookkeeping that would have
        freed it). Committed full pages park in the prefix LRU exactly
        as finish-time eviction would leave them — their K/V was fully
        written before commit_page registered them, so they stay
        safely matchable. Returns the number of slots reclaimed."""
        occupied = set(range(self.cfg.max_seqs)) - set(self._slot_free)
        for s in sorted(occupied):
            # a mid-write tail page may carry no hash; free_slot already
            # routes hashed -> LRU, unhashed -> free list. But a hashed
            # page only PARTIALLY covered by seq_lens (a crash between
            # advance and commit cannot produce one — commit follows
            # advance — so this is belt and braces) must not stay
            # matchable: rollback to the resident length first.
            self.rollback(s, int(self.seq_lens[s]))
            self.free_slot(s)
        self.stats["slots_reclaimed"] += len(occupied)
        return len(occupied)

    def alloc_slot(self) -> int:
        """Claim an empty decode slot. Pages arrive separately via
        attach_prefix (shared) and ensure_capacity (fresh)."""
        if not self._slot_free:
            raise RuntimeError("no free slot (scheduler must check "
                               "free_slots first)")
        return self._slot_free.pop()

    def attach_prefix(self, slot: int, pages: Sequence[int],
                      ntokens: int) -> None:
        """Map already-resident prefix pages into an empty slot and mark
        their `ntokens` tokens resident without any compute. Bumps each
        page's refcount (pulling refcount-0 pages out of the LRU)."""
        if self.seq_lens[slot] != 0 or self.mapped_pages(slot) != 0:
            raise RuntimeError(f"attach_prefix on non-empty slot {slot}")
        if ntokens != len(pages) * self.cfg.page_size:
            raise ValueError(
                f"prefix of {ntokens} tokens does not fill "
                f"{len(pages)} pages exactly (only whole pages share)")
        for i, p in enumerate(pages):
            p = int(p)
            if self._ref[p] == 0:
                if p not in self._lru:
                    raise RuntimeError(
                        f"page {p} has refcount 0 but is not cached")
                del self._lru[p]
            else:
                self.stats["shared_attaches"] += 1
            self._ref[p] += 1
            self.stats["max_page_refs"] = max(self.stats["max_page_refs"],
                                              int(self._ref[p]))
            self.page_tables[slot, i] = p
        self.stats["prefix_hit_pages"] += len(pages)
        self.seq_lens[slot] = ntokens

    def ensure_capacity(self, slot: int, total_tokens: int) -> int:
        """Allocate fresh (refcount-1, unhashed) pages so the slot can
        hold `total_tokens`. Returns the number of pages allocated.
        The caller (scheduler) must have verified `pages_to_extend`
        against `free_pages` — running dry here is a scheduling bug."""
        if total_tokens > self.cfg.pages_per_seq * self.cfg.page_size:
            raise ValueError(
                f"{total_tokens} tokens exceeds the page-table ceiling")
        have = self.mapped_pages(slot)
        need = self.pages_for(total_tokens)
        for i in range(have, need):
            page = self._take_page()
            self._ref[page] = 1
            self.page_tables[slot, i] = page
        return max(0, need - have)

    def pages_to_extend(self, slot: int, total_tokens: int) -> int:
        return max(0, self.pages_for(total_tokens) - self.mapped_pages(slot))

    def advance(self, slot: int, new_len: int) -> None:
        """Mark tokens up to `new_len` resident (a completed prefill
        chunk / decode write). Pages must already be mapped."""
        if new_len < int(self.seq_lens[slot]):
            raise ValueError(
                f"advance moved slot {slot} backwards "
                f"({self.seq_lens[slot]} -> {new_len})")
        if self.pages_for(new_len) > self.mapped_pages(slot):
            raise RuntimeError(
                f"slot {slot} advanced to {new_len} tokens past its "
                f"{self.mapped_pages(slot)} mapped pages")
        self.seq_lens[slot] = new_len

    def append_token(self, slot: int) -> int:
        """Advance the slot's length by one decoded token, allocating a
        page on demand when the position crosses a page boundary;
        returns the new token's position."""
        if self.seq_lens[slot] == 0:
            raise RuntimeError(f"append_token on empty slot {slot}")
        pos = int(self.seq_lens[slot])
        self.ensure_capacity(slot, pos + 1)
        self.seq_lens[slot] = pos + 1
        return pos

    def rollback(self, slot: int, new_len: int) -> int:
        """Rewind the slot to `new_len` resident tokens and unmap every
        page wholly past the new boundary. Returns the pages released.

        This is the speculative-decoding undo: rejected draft tokens
        have already scattered K/V into pages the scheduler mapped
        ahead (ensure_capacity), and once verification truncates the
        sequence those tail pages hold garbage. Positions inside the
        kept pages need no cleanup — reads are masked by seq_lens and
        the slots are overwritten when the sequence actually reaches
        them — but whole pages past `pages_for(new_len)` must leave
        the table so the pool's accounting stays exact.

        A released page is NEVER parked in the prefix LRU, and any
        hash it carries is dropped when its refcount reaches 0: its
        content is no longer vouched for by a resident sequence, so a
        post-rollback tail page must not be prefix-matchable (the
        check_invariants hashed-page-coverage rule). In the engine's
        flow these pages are always fresh refcount-1 unhashed
        allocations — commit_page only ever registers fully VERIFIED
        pages — but the method is defensive about shared/hashed ones
        so direct users cannot corrupt the registry."""
        if new_len < 0:
            raise ValueError(f"rollback to negative length {new_len}")
        ps = self.cfg.page_size
        if new_len < int(self.seq_lens[slot]):
            self.seq_lens[slot] = new_len
        released = 0
        for i in range(self.pages_for(new_len), self.cfg.pages_per_seq):
            p = int(self.page_tables[slot, i])
            if p == 0:
                break  # tables are contiguous prefixes
            self._ref[p] -= 1
            if self._ref[p] == 0:
                self._unregister(p)
                self._free.append(p)
            elif not self._vouched(p):
                self._unregister(p)   # surviving owners rolled back too
            self.page_tables[slot, i] = 0
            released += 1
        # the boundary page stays mapped when new_len cuts into it, but
        # a hash on it now overclaims (the registry key vouches for the
        # FULL page) — drop it unless another sequence still covers it
        if new_len % ps:
            p = int(self.page_tables[slot, new_len // ps])
            if p != 0 and p in self._hash_of_page and not self._vouched(p):
                self._unregister(p)
        self.stats["rollback_pages"] += released
        return released

    def _vouched(self, page: int) -> bool:
        """True when some slot's RESIDENT (seq_lens-covered) full pages
        include `page` — the condition for its content hash to stay in
        the registry (check_invariants' hashed-page coverage rule)."""
        for s in range(self.cfg.max_seqs):
            full = int(self.seq_lens[s]) // self.cfg.page_size
            if page in (int(p) for p in self.page_tables[s, :full]):
                return True
        return False

    def free_slot(self, slot: int) -> None:
        """Release the slot: every mapped page's refcount drops; pages
        reaching 0 go back to the free list — or, if content-hashed, to
        the reclaimable LRU so a future prompt can still match them.
        This is both the finished-sequence eviction path and the
        preemption path (a preempted sequence's prefix stays matchable,
        which is what makes preemption cheap to undo)."""
        for i in range(self.cfg.pages_per_seq):
            p = int(self.page_tables[slot, i])
            if p == 0:
                continue
            self._ref[p] -= 1
            if self._ref[p] == 0:
                if p in self._hash_of_page:
                    self._lru[p] = None   # most-recently parked
                else:
                    self._free.append(p)
            self.page_tables[slot, i] = 0
        self.seq_lens[slot] = 0
        self._slot_free.append(slot)

    def parked_pages(self) -> Tuple[int, ...]:
        """The prefix-cache-parked pages: complete, unreferenced,
        prefix-matchable — content that must outlive its writer for a
        later request to attach (the post-run surface
        ServeEngine.check_kv_scales audits)."""
        return tuple(int(p) for p in self._lru)

    def pool_report(self) -> Dict[str, object]:
        """The KV-pool line of ServeEngine.last_stats / serve_report:
        storage format, per-page and pool bytes (itemsize-derived),
        effective pages, and the capacity multiplier vs f32 pages.
        Occupancy here is INSTANTANEOUS (meaningful mid-run; zero once
        generate() has released every slot) — last_stats overrides it
        with the run's peak."""
        c = self.cfg
        return {
            "kv_dtype": c.kv_dtype,
            "bytes_per_page": c.page_bytes,
            "effective_pages": c.usable_pages,
            "pool_bytes": c.pool_bytes,
            "cache_bytes_per_token": c.cache_bytes_per_token,
            "cache_bytes_constant_per_seq": c.constant_bytes_per_seq,
            "tensor_parallel": c.tensor_parallel,
            "bytes_per_page_device": c.page_device_bytes,
            "pool_device_bytes": c.pool_device_bytes,
            "occupancy": 1.0 - self.free_pages / c.usable_pages,
            "page_ratio_vs_f32": round(c.effective_page_ratio, 3),
            "pages_saved_vs_f32": int(
                c.usable_pages - c.usable_pages / c.effective_page_ratio),
        }

    # ---------------- invariant checks (tests) ------------------------
    def check_invariants(self, pool: Optional[KVPool] = None) -> None:
        """Property-style asserts: refcounts equal the number of table
        references, the free/cached/mapped states partition the pool,
        no page leaks or double-frees, tables are contiguous prefixes,
        the hash registry is a consistent bijection, and `pool` (the
        engine's device pool, where given) has this geometry."""
        c = self.cfg
        table_refs: Dict[int, int] = {}
        for s in range(c.max_seqs):
            row = self.page_tables[s]
            nz = np.flatnonzero(row)
            n_mapped = len(nz)
            assert np.array_equal(nz, np.arange(n_mapped)), (
                f"slot {s} page table is not a contiguous prefix: {row}")
            assert int(self.seq_lens[s]) <= n_mapped * c.page_size, (
                f"slot {s} length {self.seq_lens[s]} exceeds its "
                f"{n_mapped} mapped pages")
            for p in row[:n_mapped]:
                table_refs[int(p)] = table_refs.get(int(p), 0) + 1
        assert 0 not in table_refs, "sink page mapped to a slot"
        free, lru = set(self._free), set(self._lru)
        assert len(free) == len(self._free), "free list has duplicates"
        assert not (free & lru), "page both free and cached"
        for p in range(1, c.num_pages):
            r = int(self._ref[p])
            assert r == table_refs.get(p, 0), (
                f"page {p} refcount {r} != {table_refs.get(p, 0)} "
                f"table references")
            states = (p in free) + (p in lru) + (r > 0)
            assert states == 1, (
                f"page {p} in {states} states (free={p in free}, "
                f"cached={p in lru}, refs={r})")
            if p in lru:
                assert p in self._hash_of_page, f"cached page {p} unhashed"
        assert len(table_refs) + len(free) + len(lru) == c.usable_pages, (
            "page leak: states do not partition the pool")
        assert len(self._hash_of_page) == len(self._page_of_hash), (
            "hash registry is not a bijection")
        for page, key in self._hash_of_page.items():
            assert self._page_of_hash.get(key) == page, (
                f"hash registry maps page {page} inconsistently")
        # a hashed (prefix-matchable) page must be VOUCHED for: either
        # parked in the LRU (its last owner completed it before
        # freeing) or fully covered by some slot's resident length. A
        # mapped page past any coverage — a speculative tail, or a
        # rolled-back region — holds unverified K/V and being matchable
        # would hand garbage to a future prompt (the rollback contract).
        covered_pages = set()
        for s in range(c.max_seqs):
            full = int(self.seq_lens[s]) // c.page_size
            covered_pages.update(int(p) for p in self.page_tables[s, :full])
        for page in self._hash_of_page:
            assert page in self._lru or page in covered_pages, (
                f"hashed page {page} is neither parked nor fully "
                f"covered by a resident sequence (rolled-back or "
                f"speculative pages must not be prefix-matchable)")
        if not self.prefix_enabled:
            assert not self._hash_of_page and not self._lru, (
                "prefix cache disabled but registry non-empty")
        # disaggregated-handoff bookkeeping: an IMPORTED page's content
        # was never computed here, so it is reachable ONLY through its
        # chain-key registration — a resident imported page without a
        # hash would be unidentifiable garbage. Every imported page
        # must therefore still be hashed (eviction/_unregister removes
        # it from the imported set atomically with its key) and in one
        # of the hashed states the coverage rule above already vouches
        # for (parked, or mapped under a resident sequence).
        for page in self._imported:
            assert page in self._hash_of_page, (
                f"imported page {page} lost its chain key while still "
                f"tracked as handoff content")
        # the slots' constant part: a ring never holds more than the
        # window, one step's chunk and a page of slack (a second where
        # window + chunk is not whole pages), and holds at least what
        # one step needs live at once
        if c.hybrid is not None:
            h, ring = c.hybrid, c.ring_pages * c.page_size
            assert not h.window_layers or h.window + h.chunk - 1 <= ring \
                < h.window + h.chunk + 2 * c.page_size, (
                f"a ring of {ring} tokens for a window of {h.window} "
                f"and chunks of {h.chunk}")
            assert self.prefix_enabled is False, (
                "a prefix hit would skip the state of the prefix")
        # the device pool this bookkeeping describes, where the caller
        # holds one: its leaves must be of this configuration's geometry
        # (a HybridPool checks its pages, rings and slabs)
        if pool is not None:
            pool.check_geometry(c)
