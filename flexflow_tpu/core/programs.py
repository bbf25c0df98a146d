"""ProgramRegistry: one owner for every jitted program in the system.

The stack's zero-recompile discipline used to be enforced ad hoc per
subsystem — serve's fixed-shape mixed program snapshotted a process-wide
jax.monitoring counter around each call, the executor cached jitted
train steps on attributes, and `compile_counts()` was the max of two
imperfect proxies (monitoring events and distinct shape signatures).
None of that helped a COLD replica: an autoscaler scale-up with no
parked replica, or a cross-process fabric worker, pays the full
first-request compile storm.

This module factors the discipline into one object:

- ``register(name, static_argnums=...)`` declares a program family
  (serve's "mixed"/"export"/..., the executor's "train_step[...]").
- ``call(name, fn, *args)`` resolves the family + argument signature to
  a compiled executable: cache hit -> dispatch, miss -> AOT
  ``fn.lower(*args).compile()`` (timed, counted) then dispatch. The
  count is EXACT per family — a compile cannot hide from it the way it
  could from the monitoring snapshot (e.g. compiles triggered inside
  warmup_handoff / adapter load).
- ``save(dir)`` / ``load_warm()`` serialize the compiled executables
  (``jax.experimental.serialize_executable``) keyed by a program
  FINGERPRINT folding model arch, lane widths, kv dtype/pool geometry,
  adapter rank/slots, tp degree and jax/backend version — a cold
  process deserializes its programs before the first request and boots
  warm (compile_counts() == 0). Corrupt/truncated stores warn and fall
  back to compiling, mirroring search/cost_cache.py's corrupt-store
  discipline; a restored executable that rejects its first call (stale
  cache from an incompatible runtime) is dropped and recompiled with a
  warning, never crashing the engine.

An executable is bound to the devices it was compiled for, so each
store entry records those device ids and ``load_warm`` restores onto
exactly them (``deserialize_and_load`` would otherwise bind to every
local device and reject the first call on any multi-device host);
callers fold their device set into the fingerprint, so replicas on
different chips keep separate stores.

The registry places the serialized executables (``*.ffprog``) only.
JAX's persistent compilation cache is placed by the entry points
through ``utils/cache_dirs.arm_compile_cache`` — never from here.

Also here, because every start resolves its programs here: what a
START costs (docs/observability.md "Set-up phases"). ``CompileEvents``
is the process's one listener on JAX's compile events;
``boot_phases()`` makes the list a model or an engine keeps its set-up
phases in; ``PROCESS_PHASES`` holds the process's own (the package's
``import``); a registry given a ``phase`` writes one
``compile:<family>`` a program it compiles or restores.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import pickle
import time
import warnings
from typing import Any, Callable, Dict, Optional

import jax

from ..utils.telemetry import PhaseList

# 2: entries record the devices they execute on
# 3: programs carry named scopes in their operations' metadata — a store
#    written before that would restore executables a profiler trace
#    cannot attribute (the fingerprint folds no metadata)
_STORE_VERSION = 3
_STORE_SUFFIX = ".ffprog"


class CompileEvents:
    """The process's ONE listener on jax.monitoring's public event
    stream: running totals of what compiling cost, whoever asked.

    '/jax/core/compile/backend_compile_duration' fires once for every
    program that reaches the backend's compiler OR is read back from
    JAX's persistent compilation cache, and never on a jit-cache hit;
    '/jax/compilation_cache/cache_hits' fires for the second kind
    alone, so ``backend_compiles - cache_hits`` programs were compiled.
    The seconds are the event's own: the compile, or the cache read.
    Tracing and lowering to MLIR come before either and are never
    cached between processes; their seconds are kept beside (a jit
    traced inside another's trace fires inside it, so ``trace_s`` counts
    nested traces twice and can pass the phase it is read over).

    Two readers. The zero-recompile gates (fit's drift sampling,
    tools/train_bench.py) diff ``count`` around a region: monkeypatch-
    free, and it catches even a same-signature recompile (a dropped jit
    cache) that a distinct-shape count would miss. Set-up phases
    (``boot_phases``) diff ``totals()``, which is what names the eager
    compiles of ``init_state`` and the pool's allocation that no
    registry sees. Single listener per process; starts are not
    concurrent, so an around-phase diff is race-free."""

    count = 0               # backend_compiles
    compile_s = 0.0
    cache_hits = 0
    trace_s = 0.0
    lower_s = 0.0
    _installed: Optional[bool] = None
    _SECONDS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    }

    @classmethod
    def install(cls) -> bool:
        if cls._installed is None:
            from jax import monitoring
            monitoring.register_event_duration_secs_listener(
                cls._on_duration)
            monitoring.register_event_listener(cls._on_event)
            cls._installed = True
        return cls._installed

    @classmethod
    def _on_duration(cls, event: str, duration: float, **kwargs) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            cls.count += 1
            cls.compile_s += duration
        elif event in cls._SECONDS:
            key = cls._SECONDS[event]
            setattr(cls, key, getattr(cls, key) + duration)

    @classmethod
    def _on_event(cls, event: str, **kwargs) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            cls.cache_hits += 1

    @classmethod
    def totals(cls) -> Dict[str, float]:
        return {"backend_compiles": cls.count,
                "backend_compile_s": cls.compile_s,
                "cache_hits": cls.cache_hits,
                "trace_s": cls.trace_s, "lower_s": cls.lower_s}


def boot_phases() -> PhaseList:
    """The list one start's set-up phases are kept in
    (``Telemetry.timed(..., keep=)``): every record carries what the
    process compiled, read from the cache, traced and lowered while the
    phase ran."""
    CompileEvents.install()
    return PhaseList(totals=CompileEvents.totals)


# the process's own phases: `import` and `jax_import`
# (flexflow_tpu/__init__.py), and what an entry point adds
# (tools/setup_phases.py: `backend_init`, `import_driver`). A model's
# and an engine's are their own lists.
PROCESS_PHASES = boot_phases()


def fingerprint_hash(fp: Dict[str, Any]) -> str:
    """Stable short hash of a fingerprint dict (the cost_cache.py
    machine_fingerprint idiom): canonical-JSON then sha256."""
    blob = json.dumps(fp, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _leaf_sig(leaf) -> tuple:
    """Signature of one flattened argument leaf. Arrays key on
    (shape, dtype, weak_type, sharding spec) — what jit's own cache
    keys on, minus the committed-device identity (a host numpy array
    and an uncommitted device array lower identically). Non-array
    leaves (static python scalars like the export/import n_pools) key
    on their VALUE, exactly as static_argnums demands."""
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        sh = getattr(leaf, "sharding", None)
        spec = getattr(sh, "spec", None)
        if spec is None:
            tok = ""
        else:
            # trailing None entries are implicit (PartitionSpec('x',)
            # == PartitionSpec('x', None) to jit) — strip them so
            # equivalent shardings key identically
            t = tuple(spec)
            while t and t[-1] is None:
                t = t[:-1]
            tok = str(t)
        return ("a", tuple(leaf.shape), str(leaf.dtype),
                bool(getattr(leaf, "weak_type", False)), tok)
    return ("s", repr(leaf))


class ProgramRegistry:
    """Shape signatures, compile counting and AOT executable caching
    for a set of named program families (one registry per engine /
    executor; families are e.g. serve's six serving functions)."""

    def __init__(self, fingerprint: Dict[str, Any],
                 cache_dir: Optional[str] = None,
                 phase: Optional[Callable] = None):
        self.fingerprint = dict(fingerprint)
        self.fp_hash = fingerprint_hash(self.fingerprint)
        self.cache_dir = cache_dir
        # the owner's set-up phase writer, `phase(name, args)` -> a
        # context manager (FFModel.setup_phase, ServeEngine.
        # setup_phase); without one nothing is written
        self._phase = phase or (lambda name, args=None:
                                contextlib.nullcontext())
        self._restore_s = 0.0
        self._statics: Dict[str, tuple] = {}          # family -> argnums
        self._compiled: Dict[tuple, Any] = {}         # (family, sig) ->
        self._restored_keys: set = set()              # Compiled
        self._compiles: Dict[str, int] = {}
        self._restored: Dict[str, int] = {}
        self._compile_s: Dict[str, float] = {}
        self._dirty = False

    # ---------------- registration / resolution -----------------------
    def register(self, name: str, *, static_argnums: tuple = ()) -> None:
        self._statics[name] = tuple(static_argnums)
        self._compiles.setdefault(name, 0)
        self._restored.setdefault(name, 0)
        self._compile_s.setdefault(name, 0.0)

    def families(self) -> tuple:
        return tuple(self._statics)

    def signature(self, args, extra_key: Optional[str] = None) -> str:
        leaves, treedef = jax.tree_util.tree_flatten(args)
        parts = [str(treedef)]
        parts.extend(repr(_leaf_sig(l)) for l in leaves)
        if extra_key is not None:
            parts.append(extra_key)
        return hashlib.sha256(
            "\x1f".join(parts).encode()).hexdigest()[:24]

    def _compile(self, name: str, fn, args) -> Any:
        t0 = time.perf_counter()
        with self._phase("compile:" + name, {
                "fingerprint": self.fp_hash, "source": "compiled"}):
            compiled = fn.lower(*args).compile()
        self._compile_s[name] = self._compile_s.get(name, 0.0) \
            + (time.perf_counter() - t0)
        self._compiles[name] = self._compiles.get(name, 0) + 1
        self._dirty = True
        return compiled

    def call(self, name: str, fn, *args, extra_key: Optional[str] = None):
        """Resolve (family, signature) to a compiled executable and
        dispatch it. New signature -> AOT compile (exact counting);
        restored executable that rejects the call -> warn, drop, and
        recompile (stale-cache rejection: a bad cache costs a compile
        and a warning, never a crash). `extra_key` folds caller context
        the arguments cannot express into the cache key — the executor
        uses it for build-variant tokens (sparse routing, scan vs
        unroll, optimizer hyperparameters) whose flip changes the
        program without changing any argument shape."""
        if name not in self._statics:
            self.register(name)
        statics = self._statics.get(name, ())
        if not hasattr(fn, "lower"):   # not a jit wrapper: dispatch
            return fn(*args)           # directly (fallback path)
        key = (name, self.signature(args, extra_key))
        compiled = self._compiled.get(key)
        if compiled is None:
            compiled = self._compile(name, fn, args)
            self._compiled[key] = compiled
        dyn = [a for i, a in enumerate(args) if i not in statics]
        try:
            return compiled(*dyn)
        except (TypeError, ValueError, jax.errors.JaxRuntimeError) as e:
            if key not in self._restored_keys:
                raise
            # deserialized from a snapshot whose runtime disagrees
            # with ours in a way the fingerprint did not fold (jit's
            # own argument checks raise TypeError/ValueError; the
            # runtime's — wrong shard count, foreign device — raise
            # JaxRuntimeError) — reject the stale entry, compile fresh
            warnings.warn(
                f"program cache: restored {name!r} executable rejected "
                f"its first call ({e}); recompiling", stacklevel=2)
            self._restored_keys.discard(key)
            self._restored[name] = max(0, self._restored.get(name, 1) - 1)
            compiled = self._compile(name, fn, args)
            self._compiled[key] = compiled
            return compiled(*dyn)

    # ---------------- accounting ---------------------------------------
    def compile_counts(self) -> Dict[str, int]:
        """EXACT compiles per registered family this process performed
        (restored-from-snapshot executables count zero — that is the
        warm-boot contract)."""
        return {name: self._compiles.get(name, 0)
                for name in self._statics}

    def restored_counts(self) -> Dict[str, int]:
        return {name: self._restored.get(name, 0)
                for name in self._statics}

    def compile_seconds(self) -> float:
        return float(sum(self._compile_s.values()))

    def boot_record(self) -> Dict[str, Any]:
        """What booting this registry cost — the autoscaler's cold-vs-
        warm price and the `replica_boot` span payload."""
        return {
            "fingerprint": self.fp_hash,
            "restored": int(sum(self._restored.values())),
            "compiles": int(sum(self._compiles.values())),
            "compile_s": self.compile_seconds(),
            "restore_s": self._restore_s,   # load_warm's reads
            "families": {n: {"compiles": self._compiles.get(n, 0),
                             "restored": self._restored.get(n, 0),
                             "compile_s": round(
                                 self._compile_s.get(n, 0.0), 4)}
                         for n in self._statics},
        }

    # ---------------- persistence --------------------------------------
    def _store_path(self, cache_dir: Optional[str] = None) -> str:
        d = cache_dir if cache_dir is not None else self.cache_dir
        return os.path.join(d, self.fp_hash + _STORE_SUFFIX)

    def save(self, cache_dir: Optional[str] = None) -> int:
        """Serialize every compiled executable to
        ``<dir>/<fp_hash>.ffprog`` (atomic temp-then-replace, the
        checkpoint.py discipline) plus a human-readable manifest.
        Merges with a valid existing store for the same fingerprint
        (two engines over one dir each contribute their programs).
        Returns the number of entries written."""
        d = cache_dir if cache_dir is not None else self.cache_dir
        if not d:
            return 0
        os.makedirs(d, exist_ok=True)
        path = self._store_path(d)
        entries: Dict[tuple, dict] = {}
        old = self._read_store(path)
        if old is not None:
            for e in old.get("entries", []):
                entries[(e["family"], e["sig"])] = e
        from jax.experimental.serialize_executable import serialize
        for (family, sig), compiled in self._compiled.items():
            try:
                payload, in_tree, out_tree = serialize(compiled)
            except Exception as e:   # an unserializable executable is
                warnings.warn(       # skipped, not fatal
                    f"program cache: could not serialize {family!r} "
                    f"({e}); skipping", stacklevel=2)
                continue
            entries[(family, sig)] = {
                "family": family, "sig": sig,
                "statics": list(self._statics.get(family, ())),
                "device_ids": [d.id for d in compiled
                               .runtime_executable().local_devices()],
                "payload": payload, "in_tree": in_tree,
                "out_tree": out_tree,
                "compile_s": self._compile_s.get(family, 0.0),
            }
        blob = pickle.dumps({
            "version": _STORE_VERSION,
            "fingerprint": self.fingerprint,
            "fp_hash": self.fp_hash,
            "jax": jax.__version__,
            "entries": list(entries.values()),
        })
        tmp = f"{path}.tmp-{os.getpid()}"
        with open(tmp, "wb") as f:
            f.write(blob)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        self._write_manifest(d, len(entries))
        self._dirty = False
        return len(entries)

    def _write_manifest(self, d: str, n_entries: int) -> None:
        """Best-effort human-readable sidecar: which fingerprints live
        in this dir and what they hold (the store itself is pickle)."""
        path = os.path.join(d, "manifest.json")
        try:
            doc = {}
            if os.path.exists(path):
                with open(path) as f:
                    doc = json.load(f)
            if not isinstance(doc, dict):
                doc = {}
        except (OSError, json.JSONDecodeError, UnicodeDecodeError):
            doc = {}
        doc[self.fp_hash] = {
            "entries": n_entries,
            "families": sorted(self._statics),
            "jax": jax.__version__,
            "fingerprint": {k: str(v)
                            for k, v in self.fingerprint.items()},
        }
        try:
            tmp = f"{path}.tmp-{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
        except OSError:
            pass

    def _read_store(self, path: str) -> Optional[dict]:
        """Read + validate a store file. Any corruption (truncated
        pickle, wrong type, wrong version, foreign fingerprint) warns
        and returns None — the caller compiles cold. Mirrors
        cost_cache.py: a bad cache costs a warning, never a crash."""
        if not os.path.exists(path):
            return None
        try:
            with open(path, "rb") as f:
                doc = pickle.loads(f.read())
            if (not isinstance(doc, dict)
                    or doc.get("version") != _STORE_VERSION
                    or not isinstance(doc.get("entries"), list)):
                raise ValueError("malformed program store")
            if doc.get("fp_hash") != self.fp_hash:
                # a DIFFERENT program fingerprint under the same file
                # name: treat as a miss (and as corrupt for merge —
                # save() will overwrite wholesale)
                return None
        except Exception as e:
            warnings.warn(
                f"program cache: unreadable store {path!r} ({e}); "
                f"booting cold", stacklevel=2)
            return None
        return doc

    def load_warm(self, cache_dir: Optional[str] = None) -> int:
        """Deserialize every stored executable for this fingerprint.
        Returns the number restored (0 on miss/corruption — never
        raises). Call AFTER register() so family static-argnums are
        known."""
        d = cache_dir if cache_dir is not None else self.cache_dir
        if not d:
            return 0
        path = self._store_path(d)
        t0 = time.perf_counter()
        args = {"restored": 0, "store_bytes": os.path.getsize(path)
                if os.path.exists(path) else 0}
        with self._phase("load_programs", args):
            args["restored"] = self._load_store(path)
        self._restore_s += time.perf_counter() - t0
        return args["restored"]

    def _load_store(self, path: str) -> int:
        doc = self._read_store(path)
        if doc is None:
            return 0
        from jax.experimental.serialize_executable import \
            deserialize_and_load
        by_id = {d.id: d for d in jax.devices()}
        n = 0
        for e in doc["entries"]:
            try:
                family = e["family"]
                key = (family, e["sig"])
                # onto the devices it was compiled for: the default
                # binds to ALL local devices, and the first call then
                # fails with "expected N shards"
                with self._phase("compile:" + family, {
                        "fingerprint": self.fp_hash,
                        "source": "restored"}):
                    compiled = deserialize_and_load(
                        e["payload"], e["in_tree"], e["out_tree"],
                        execution_devices=[by_id[i]
                                           for i in e["device_ids"]])
            except Exception as exc:
                warnings.warn(
                    f"program cache: could not deserialize a "
                    f"{e.get('family')!r} executable ({exc}); it will "
                    f"be recompiled", stacklevel=2)
                continue
            if family not in self._statics:
                self.register(family,
                              static_argnums=tuple(e.get("statics", ())))
            self._compiled[key] = compiled
            self._restored_keys.add(key)
            self._restored[family] = self._restored.get(family, 0) + 1
            n += 1
        return n

    @classmethod
    def load(cls, cache_dir: str,
             fingerprint: Dict[str, Any]) -> "ProgramRegistry":
        """Build a registry for `fingerprint` and warm it from
        `cache_dir` in one step (the cold-replica boot path)."""
        reg = cls(fingerprint, cache_dir=cache_dir)
        reg.load_warm()
        return reg
