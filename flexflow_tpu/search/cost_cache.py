"""Persistent per-op cost cache for the strategy search.

The reference keeps its measurement cache alive for exactly one search
run (hash-keyed in-memory map, simulator.cc:301-321); every new process
re-measures. Here the simulator's per-(op, op-strategy) costs — analytic
roofline numbers and, with FFConfig.measure_top_ops, measured-grounded
ones — are serialized to disk keyed by

    (op signature, shard/axis-map signature, machine-model fingerprint)

so repeated searches, `enumerate_mesh_shapes` sweeps, and tools
(sim_validation, search_bench) skip re-deriving and re-measuring costs
entirely. The machine-model fingerprint covers the MachineSpec numbers,
calibrated efficiency factors, torus/DCN layout, and mesh shape: any
change to what the cost formulas would see invalidates the entries
(stale entries for other fingerprints are kept in the file, not used).

Path: costcache.json under utils/cache_dirs.measurement_cache_dir()
(with the compile cache; root overridable via FLEXFLOW_TPU_CACHE like
the calibration caches, file overridable via FFConfig.cost_cache_file /
--cost-cache). One CostCache object per
path is shared process-wide — parallel annealing chains read and write
the same store under a lock.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import threading
from typing import Dict, Optional

# row layout of a persisted OpCost; adding a field widens the row, and
# get()'s length check makes every pre-widening row a clean miss (the
# COST_MODEL_VERSION bump in the fingerprint retires them anyway)
_COST_FIELDS = ("fwd", "bwd", "fwd_comm", "bwd_comm", "sync", "mem",
                "update", "sync_bytes")


_PRICING_SRC_HASH: Optional[str] = None


def _pricing_source_hash() -> str:
    """Hash of the pricing-code sources (cost_model, machine_model,
    op_measure): an edited cost formula changes the fingerprint
    automatically, so stale cache entries can never be served by a
    forgotten COST_MODEL_VERSION bump. Memoized per process."""
    global _PRICING_SRC_HASH
    if _PRICING_SRC_HASH is None:
        h = hashlib.sha256()
        base = os.path.dirname(os.path.abspath(__file__))
        for mod in ("cost_model.py", "machine_model.py",
                    "op_measure.py", "serve_place.py"):
            try:
                with open(os.path.join(base, mod), "rb") as f:
                    h.update(f.read())
            except OSError:
                h.update(mod.encode())  # zipped install: name only
        _PRICING_SRC_HASH = h.hexdigest()[:16]
    return _PRICING_SRC_HASH


def machine_fingerprint(mm, mesh=None, precision=None,
                        overlap=None, serve=None) -> str:
    """Stable short hash of everything the cost formulas read from the
    machine model + mesh (plus the pricing code itself). Shared by the
    cost cache, sim_validation and perf_report so committed numbers are
    attributable to one machine state without re-measuring it.

    `precision` is the (compute_dtype, param_dtype) policy the costs
    were priced under (cost_model.op_precision): a dtype flip changes
    every byte/flops figure, so entries cached for f32 pricing must
    MISS for a bf16 search (and vice versa) — regression-tested in
    tests/test_mixed_precision.py. Per-dtype efficiency factors
    ("matmul:float32") ride the efficiency dict already hashed here.

    `overlap` is the runtime's sync-overlap configuration the simulator
    priced under — (search_overlap_backward_sync, grad_bucket_mb), see
    Simulator.overlap_sig(): an overlap flip or a bucket-size change
    alters every simulated makespan the cached numbers feed, so it must
    be a guaranteed cache miss (regression-tested in
    tests/test_overlap.py).

    `serve` is the serve-placement signature (search/serve_place:
    tensor degree, axis assignment, KV/activation dtypes) the serve
    pricing ran under: a placement or page-dtype flip changes the KV
    streaming and collective bytes of every serve-step cost, so cached
    serve entries must MISS across it (tests/test_serve_shard.py)."""
    from .cost_model import COST_MODEL_VERSION
    spec = {f.name: getattr(mm.spec, f.name, None)
            for f in dataclasses.fields(mm.spec)}
    blob = {
        "costmodel_v": COST_MODEL_VERSION,
        "pricing_src": _pricing_source_hash(),
        "spec": {k: (list(v) if isinstance(v, tuple) else v)
                 for k, v in spec.items()},
        "efficiency": dict(sorted(mm.efficiency.items())),
        "dtype_flops_scale": dict(sorted(
            getattr(mm, "dtype_flops_scale", {}).items())),
        "dcn_axes": list(mm.dcn_axes),
        "axis_topology": {k: list(v)
                          for k, v in sorted(mm.axis_topology.items())},
        "mesh": (sorted(mesh.shape.items()) if mesh is not None else None),
        "precision": (list(str(p) for p in precision)
                      if precision is not None else None),
        "overlap": (list(overlap) if overlap is not None else None),
        "serve": (list(serve) if serve is not None else None),
    }
    raw = json.dumps(blob, sort_keys=True, default=str)
    return hashlib.sha256(raw.encode()).hexdigest()[:16]


def default_path() -> str:
    from ..utils.cache_dirs import measurement_cache_dir
    return os.path.join(measurement_cache_dir(), "costcache.json")


class CostCache:
    """Disk-backed {entry key -> OpCost} map, scoped to one machine
    fingerprint. Pipeline-expanded costs (OpCost.pipeline) carry nested
    schedule state and are never persisted."""

    _open: Dict[str, "CostCache"] = {}
    _open_lock = threading.Lock()

    def __init__(self, path: str):
        self.path = path
        self._lock = threading.Lock()
        # fingerprint -> {key -> [len(_COST_FIELDS) floats]}
        self._data: Dict[str, Dict[str, list]] = {}
        self._dirty = False
        self._loaded = False
        self.hits = 0
        self.misses = 0

    @classmethod
    def open(cls, path: Optional[str] = None) -> "CostCache":
        """Process-wide shared instance per path (parallel chains and
        mesh-shape sweeps must see one read-mostly store)."""
        path = path or default_path()
        with cls._open_lock:
            if path not in cls._open:
                cls._open[path] = cls(path)
            return cls._open[path]

    # ---- keying ----
    @staticmethod
    def entry_key(op_sig: str, axis_sig, extra=()) -> str:
        raw = json.dumps([op_sig, list(axis_sig), list(extra)],
                         default=str)
        return hashlib.sha256(raw.encode()).hexdigest()[:24]

    # ---- I/O ----
    def _ensure_loaded(self) -> None:
        if self._loaded:
            return
        self._loaded = True
        try:
            with open(self.path) as f:
                data = json.load(f)
        except FileNotFoundError:
            return             # no cache yet — the common first run
        except (OSError, json.JSONDecodeError, UnicodeDecodeError) as e:
            # a corrupted / truncated store (crash mid-write on an old
            # build, disk fault, manual edit) must never crash a
            # search: warn, start empty, and let the next flush()
            # REBUILD the file wholesale (see flush's corrupt-merge
            # path). The cache is a pure accelerator — losing it costs
            # re-derivation, never correctness.
            import warnings
            warnings.warn(
                f"cost cache {self.path} is unreadable "
                f"({type(e).__name__}: {e}); rebuilding it from scratch")
            self._dirty = True   # next flush overwrites the wreck
            return
        if isinstance(data, dict):
            # row-level validation happens in get() (len check); here
            # just drop structurally-foreign subtrees
            self._data = {fp: dict(entries)
                          for fp, entries in data.items()
                          if isinstance(entries, dict)}

    def get(self, fingerprint: str, key: str):
        from .cost_model import OpCost
        with self._lock:
            self._ensure_loaded()
            row = self._data.get(fingerprint, {}).get(key)
            if row is None or len(row) != len(_COST_FIELDS):
                self.misses += 1
                return None
            self.hits += 1
            return OpCost(**{f: float(v)
                             for f, v in zip(_COST_FIELDS, row)})

    def put(self, fingerprint: str, key: str, cost) -> None:
        if cost.pipeline is not None:
            return
        with self._lock:
            self._ensure_loaded()
            self._data.setdefault(fingerprint, {})[key] = [
                float(getattr(cost, f)) for f in _COST_FIELDS]
            self._dirty = True

    def flush(self) -> None:
        """Atomic write (tmp + rename), merging entries another process
        may have written since we loaded. Unwritable cache paths never
        abort a search (same policy as measure.py)."""
        with self._lock:
            if not self._dirty:
                return
            try:
                os.makedirs(os.path.dirname(self.path), exist_ok=True)
                merged = {}
                try:
                    with open(self.path) as f:
                        on_disk = json.load(f)
                    if isinstance(on_disk, dict):
                        merged = {fp: e for fp, e in on_disk.items()
                                  if isinstance(e, dict)}
                except FileNotFoundError:
                    pass
                except (OSError, json.JSONDecodeError,
                        UnicodeDecodeError):
                    # corrupt on-disk store: do not merge garbage —
                    # this flush rewrites it wholesale from the
                    # in-memory entries (the rebuild _ensure_loaded
                    # promised)
                    import warnings
                    warnings.warn(
                        f"cost cache {self.path} was corrupt at flush; "
                        f"overwriting with this process's entries")
                for fp, entries in self._data.items():
                    merged.setdefault(fp, {}).update(entries)
                # the shared temp-then-os.replace primitive: a kill
                # mid-flush leaves the previous complete store, never
                # a truncation (and "cache.commit" is a stageable
                # chaos kill point like ckpt.commit/loader.commit)
                from ..core.checkpoint import atomic_write_json
                atomic_write_json(self.path, merged,
                                  fault_site="cache.commit")
                self._dirty = False
            except OSError:
                pass

    def stats(self) -> Dict[str, int]:
        with self._lock:
            n = sum(len(v) for v in self._data.values())
            return {"hits": self.hits, "misses": self.misses,
                    "entries": n}
