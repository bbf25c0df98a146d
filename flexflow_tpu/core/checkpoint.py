"""Checkpoint / resume via orbax, crash-safe.

The reference has NO training-state serialization (SURVEY.md section 5:
"no model-state serialization to disk"); the closest artifacts are host
get/set of weights and strategy files. This is the planned-in recovery
story: full TrainState (params, states, opt_state, step) saved with
orbax, with optional async saves so the step loop never blocks.

Crash safety (docs/robustness.md): every save lands in a `<path>.tmp`
staging directory and is PROMOTED onto `<path>` with atomic renames
only once fully written — a process killed at any instant leaves
either the previous complete checkpoint or none at the final name,
never a truncated one. Resume scans (FFModel.fit) therefore only ever
see committed state, and a kill-mid-save run resumes from the newest
committed epoch with a loss trajectory bit-identical to an
uninterrupted run (tests/test_faults.py). The promote point carries a
fault-injection site ("ckpt.commit", utils/faults) so chaos tests can
stage the kill deterministically.
"""

from __future__ import annotations

import json
import os
import shutil
from typing import Optional

import jax
import numpy as np

from ..utils.faults import default_injector
from .executor import TrainState


def _checkpointer(use_async: bool = False):
    import orbax.checkpoint as ocp
    if use_async:
        return ocp.AsyncCheckpointer(ocp.StandardCheckpointHandler())
    return ocp.Checkpointer(ocp.StandardCheckpointHandler())


def _promote(tmp: str, final: str) -> None:
    """Swing `final` to the fully-written `tmp` directory. Each step is
    a whole-directory rename, so no reader ever observes a
    partially-written checkpoint at `final`: a kill before the swap
    leaves the old checkpoint, a kill inside the two-rename window
    leaves it recoverable at `<final>.old` (readers run
    :func:`recover_promoted` first), and a kill after leaves the new
    one plus a stale `.old` the next promote sweeps."""
    old = final + ".old"
    if os.path.isdir(old) and os.path.isdir(final):
        shutil.rmtree(old)      # stale leftover from a killed promote
    if os.path.isdir(final):
        os.rename(final, old)
    # the narrow not-atomic window: final is absent, the previous
    # checkpoint complete at .old, the new one complete at tmp
    default_injector().fire("ckpt.swap")
    os.rename(tmp, final)
    if os.path.isdir(old):
        shutil.rmtree(old)


def recover_promoted(path: str) -> None:
    """Heal a promote killed inside its rename window: if nothing is
    committed at `path` but a complete previous checkpoint sits at
    `<path>.old`, swing it back. Idempotent; called by every reader
    (restore_checkpoint, fit's resume scan)."""
    if not os.path.isdir(path) and os.path.isdir(path + ".old"):
        os.rename(path + ".old", path)


def _payload(state: TrainState) -> dict:
    return {
        "params": state.params,
        "states": state.states,
        "opt_state": state.opt_state,
        "step": state.step,
    }


class AsyncSaver:
    """Async checkpointing with DEFERRED atomic promotes.

    orbax's AsyncCheckpointer writes in a background thread; the
    promote of save N happens when save N+1 starts (orbax would
    serialize against the in-flight write there anyway) or at
    wait_until_finished()/close(). Until its promote, a save is
    invisible at the final path — exactly the crash contract of the
    sync path, stretched over the async pipeline."""

    def __init__(self):
        self._ckptr = _checkpointer(use_async=True)
        self._pending: Optional[tuple] = None

    def save(self, path: str, state: TrainState,
             force: bool = True) -> None:
        self._commit_pending()
        path = os.path.abspath(path)
        default_injector().fire("ckpt.save")
        self._ckptr.save(path + ".tmp", _payload(state), force=force)
        self._pending = (path + ".tmp", path)

    def _commit_pending(self) -> None:
        if self._pending is None:
            return
        tmp, final = self._pending
        self._ckptr.wait_until_finished()
        # the staged kill point: tmp is complete, final not yet swung
        default_injector().fire("ckpt.commit")
        _promote(tmp, final)
        self._pending = None

    def wait_until_finished(self) -> None:
        self._commit_pending()

    def close(self) -> None:
        self._commit_pending()
        self._ckptr.close()


def save_checkpoint(path: str, state: TrainState,
                    use_async: bool = False, force: bool = True,
                    checkpointer=None):
    """Save a TrainState to `path` (a directory), atomically: the write
    lands in `<path>.tmp` and is renamed onto `path` only when
    complete, so a kill at any instant leaves no truncated checkpoint
    visible at `path`.

    With use_async=True the write happens in a background thread and an
    :class:`AsyncSaver` is RETURNED — the caller must keep it and call
    wait_until_finished() (or close()) before relying on the checkpoint
    or exiting; the checkpoint is uncommitted (invisible at `path`)
    until then. Pass the returned saver back as `checkpointer` on
    subsequent saves to reuse it (one background thread for the whole
    loop instead of one per save)."""
    if use_async:
        saver = checkpointer if checkpointer is not None else AsyncSaver()
        saver.save(path, state, force=force)
        return saver
    path = os.path.abspath(path)
    default_injector().fire("ckpt.save")
    ckptr = checkpointer or _checkpointer(False)
    ckptr.save(path + ".tmp", _payload(state), force=force)
    # the staged kill point: tmp is complete, path not yet swung
    default_injector().fire("ckpt.commit")
    _promote(path + ".tmp", path)
    if checkpointer is None:
        ckptr.close()
    return None


def atomic_write_json(path: str, obj,
                      fault_site: str = "ckpt.commit") -> None:
    """temp-then-os.replace JSON write: the file at `path` is either
    the previous complete content or the new complete content, never a
    truncation. The shared primitive for every small host-side state
    file (data-loader state, tools' artifacts that need the
    guarantee); `fault_site` names the staged kill point."""
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(obj, f)
        f.flush()
        os.fsync(f.fileno())
    default_injector().fire(fault_site)
    os.replace(tmp, path)


def restore_checkpoint(path: str, state: TrainState) -> TrainState:
    """Restore into the structure (and shardings) of `state`.

    An INFERENCE-compiled model (opt_state == {}) restores a TRAINING
    checkpoint by reading params/states/step only — the on-disk
    optimizer slots are skipped, not structure-mismatched, so the
    train -> checkpoint -> serve flow works (reference COMP_MODE
    semantics; its nearest artifact was host weight import)."""
    import orbax.checkpoint as ocp
    recover_promoted(os.path.abspath(path))
    ckptr = _checkpointer(False)
    target = {
        "params": state.params,
        "states": state.states,
        "opt_state": state.opt_state,
        "step": state.step,
    }
    if not state.opt_state:
        partial = {k: v for k, v in target.items() if k != "opt_state"}
        # the PyTree handler reads the Standard layout and supports
        # partial restore (skip the on-disk optimizer slots entirely)
        pt = ocp.Checkpointer(ocp.PyTreeCheckpointHandler())
        restored = pt.restore(
            os.path.abspath(path),
            args=ocp.args.PyTreeRestore(item=partial,
                                        partial_restore=True))
        pt.close()
        restored["opt_state"] = {}
    else:
        restored = ckptr.restore(
            os.path.abspath(path),
            args=ocp.args.StandardRestore(target))
    ckptr.close()
    return TrainState(restored["params"], restored["states"],
                      restored["opt_state"], restored["step"])


def save_model(model, path: str, use_async: bool = False):
    """Returns the AsyncCheckpointer when use_async=True (see
    save_checkpoint), else None."""
    return save_checkpoint(path, model.state, use_async=use_async)


def restore_model(model, path: str) -> None:
    model.state = restore_checkpoint(path, model.state)
    # resync the per-step training-rng mirror so the restored run's
    # stochastic ops (dropout) continue the exact stream of the
    # uninterrupted one (FFModel._train_rng keys on this counter)
    model._host_step = int(model.state.step)
