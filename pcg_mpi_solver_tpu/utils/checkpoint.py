"""In-solve checkpoint/resume.

The reference has NO in-solve checkpointing: its pipeline is resumable only
at stage granularity because every stage persists its output to disk, and
per-frame exports double as (unexploited) restart data (reference:
SURVEY.md §5; pcg_solver.py:891-894 persists per-frame ResVecData).  This
module closes that gap for the multi-step quasi-static schedule: after any
completed time step the full solver state (solution vector, convergence
histories, export counters) can be written and a later run continues from
the next step, producing byte-identical histories and export frames.

Two record granularities share the directory and the fingerprint guard:

* ``ckpt_{t:06d}.npz`` — full solver state after COMPLETED step ``t``
  (:class:`CheckpointManager`), plus the atomically-published ``latest``
  pointer.  When the pointer references a missing/corrupt file, resume
  falls back to the newest valid checkpoint instead of failing.
* ``snap_{t:06d}.npz`` — mid-Krylov snapshot INSIDE step ``t``
  (:class:`SnapshotStore`, resilience subsystem): the resumable dispatch
  carry of the chunked budget loop, persisted every N chunks so a killed
  process or lost device loses at most one snapshot interval and
  ``--resume`` continues mid-solve with bit-identical history.
* ``step_{t:06d}.npz`` — full kinematic state after COMPLETED timestep
  ``t`` of a dynamics/Newmark time history (:class:`SnapshotStore` with
  ``prefix="step"``, driven by ``resilience/engine.TimeHistoryGuard``):
  kill-and-resume continues MID-TIME-HISTORY with bit-identical
  probe/frame history, and on-disk retention is bounded to the newest K
  files (``PCG_TPU_SNAP_KEEP``).
* ``many_{t:06d}.npz`` — mid-solve blocked carry of a batched multi-RHS
  solve (:class:`SnapshotStore.for_many_solver`, driven by
  ``resilience/engine.run_many_with_recovery``): the fingerprint embeds
  the block width AND the rhs content hash.  Retention pruning and the
  corrupt-tolerant :meth:`SnapshotStore.latest` pointer are
  PREFIX-SCOPED, so they govern this namespace exactly like
  ``snap_*``/``step_*`` (asserted in tests/test_pcg_many.py).

A fingerprint of the model and solver configuration guards all of them
against resuming with mismatched state.
"""

from __future__ import annotations

import glob as _glob
import hashlib
import json
import os
import warnings
from typing import Any, Dict, Optional, Tuple

import numpy as np


def _model_hash(solver) -> str:
    """Content hash of the model the solver was built from: resuming a
    checkpoint against a model with identical shapes but different material
    fields / loads / partitioning would silently produce garbage."""
    h = hashlib.sha256()
    m = getattr(solver, "_model", None)
    if m is not None:
        for arr in (m.ck, m.cm, m.ce, m.F, m.Ud, m.fixed_dof,
                    m.elem_type, m.elem_dofs_flat, m.elem_sign_flat,
                    m.node_coords):
            a = np.ascontiguousarray(arr)
            h.update(str(a.dtype).encode())
            h.update(a.tobytes())
        # The material law enters only via the per-type element matrices
        # (e.g. a different Poisson ratio changes Ke but none of the arrays
        # above) and mat_prop — hash them too.
        for t in sorted(m.elem_lib):
            h.update(np.ascontiguousarray(m.elem_lib[t]["Ke"]).tobytes())
        # Material identity is POSITIONAL (poly_mat indexes mat_prop, e.g.
        # nonlocal_stress.py groups by poly_mat==m) — keep list order and
        # canonicalize key order recursively (incl. nested param dicts).
        h.update(json.dumps(m.mat_prop, sort_keys=True,
                            default=repr).encode())
    ep = getattr(solver.pm, "elem_part", None)
    if ep is not None:
        h.update(np.ascontiguousarray(ep).tobytes())
    return h.hexdigest()


def _process_count() -> int:
    """Process-group size for the fingerprint, without importing jax as
    a side effect of loading this module (a backend not initialized yet
    reads as single-process — the value every legacy record implies)."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return 1
    try:
        return int(jax.process_count())
    except Exception:                                   # noqa: BLE001
        return 1


def _fingerprint(solver) -> dict:
    """Everything that must not drift between checkpoint and resume: the
    model content, the numerics (precision/tol), the schedule values, and
    the export/plot config (counters in the state refer to them)."""
    cfg = solver.config
    th = cfg.time_history
    return {
        "model_hash": _model_hash(solver),
        "glob_n_dof": int(solver.pm.glob_n_dof),
        "n_parts": int(solver.pm.n_parts),
        "n_loc": int(solver.pm.n_loc),
        "dtype": str(np.dtype(solver.dtype)),
        "precision_mode": cfg.solver.precision_mode,
        "precond": cfg.solver.precond,
        # MG-shape components (ISSUE 10): the V-cycle's level count /
        # smoothing degree / lattice dims reshape both the traced apply
        # and its numerical sequence — a resume across any of them must
        # fail as a named mismatch.  "n/a" for non-mg solvers (and for
        # every pre-mg record via the restore/load legacy shims).
        "mg_shape": _mg_shape(solver),
        # the PCG loop formulation reshapes the resumable carry pytree
        # itself (the fused variant rides q/alpha/fresh recurrence
        # leaves) and changes the iteration sequence — a cross-variant
        # resume must fail HERE, as a clear fingerprint mismatch, not as
        # a pytree/in_specs error deep in the shard_map dispatch
        "pcg_variant": getattr(cfg.solver, "pcg_variant", "classic"),
        # RHS-block width: the quasi-static/dynamics solve paths are
        # always width 1; solve_many snapshots override this with the
        # actual block width (SnapshotStore.for_many_solver), so a
        # blocked resume against a different-width block fails HERE as a
        # clear fingerprint mismatch instead of a pytree shape error
        # deep in the shard_map dispatch
        "nrhs": 1,
        "tol": float(cfg.solver.tol),
        "max_iter": int(cfg.solver.max_iter),
        # every remaining trace-affecting numerics knob (found
        # mechanically by the analysis/ fingerprint-completeness rule —
        # the PR-5/PR-6 bug class, closed wholesale): the reduction
        # accumulation dtype, the MATLAB stagnation window, the mixed
        # engine's cycle tolerance + exit knobs, and the in-graph trace
        # ring length (the ring rides the resumable carry pytree, so a
        # different length is a different carry shape).
        "dot_dtype": str(np.dtype(cfg.solver.dot_dtype)),
        "max_stag_steps": int(cfg.solver.max_stag_steps),
        "inner_tol": float(cfg.solver.inner_tol),
        "mixed_knobs": [int(cfg.solver.mixed_plateau_window),
                        int(cfg.solver.mixed_progress_window),
                        float(cfg.solver.mixed_progress_ratio),
                        float(cfg.solver.mixed_progress_min_gain)],
        "trace_len": int(getattr(solver, "trace_len", 0)),
        # process-group shape: shard-per-rank snapshot epochs
        # (resilience/distributed.GroupSnapshotStore) are written by N
        # cooperating processes; a same-count resume must match, and a
        # different-count restore is only legal through the NAMED
        # elastic path (Solver.resume_elastic), never silently.
        "n_procs": _process_count(),
        "deltas": [float(d) for d in th.time_step_delta],
        "export": [bool(th.export_flag), int(th.export_frame_rate),
                   [int(f) for f in th.export_frames], th.export_vars],
        "plot": [bool(th.plot_flag), [int(d) for d in th.probe_dofs]],
        "backend": solver.backend,
        # the hybrid level-grid block layout reorders the pad-accumulate
        # sums (pinned on the ops at construction — the env knobs cannot
        # drift between trace and save), and KD (the dense/heavy split of
        # the combine maps) reorders the slot accumulation, frozen in the
        # partition's built maps
        "level_dims": [list(d) for d in getattr(solver.ops, "level_dims",
                                                ())],
        "combine_kd": _combine_kd(solver),
        # the general-form f64 refresh (hybrid+mixed) reorders the
        # refresh-residual summation — pinned on the solver at
        # construction like the kernel variant
        "f64_refresh": getattr(solver, "f64_refresh", "stencil"),
    }


def _mg_shape(solver):
    """The structural MG components of a solver configured with
    precond='mg' (driver/newmark stamp ``_mg_meta`` at setup), else
    "n/a" — JSON-stable for the fingerprint compare."""
    meta = getattr(solver, "_mg_meta", None)
    if not meta:
        return "n/a"
    return [int(meta["levels"]), int(meta["degree"]),
            [int(v) for v in meta["dims"]]]


def _combine_kd(solver) -> int | str:
    # only hybrid partitions with level grids have combine maps
    cm = getattr(getattr(solver, "pm", None), "combine", None)
    return cm.kd if cm is not None else "n/a"


#: fingerprint entries that older checkpoints recorded for matvec paths
#: the program no longer has -> (the path's name, the recorded values
#: that mean the path that stays).  False is the XLA stencil before
#: kernel variants were named; "n/a" a backend without the knob.
_REMOVED_PATHS = {
    "pallas": ("the Pallas matvec kernel", ("off", False)),
    "matvec_form": ("the corner/gsplit stencil forms", ("gse", "n/a")),
    "combine": ("the hybrid scatter combine", ("gather", "n/a")),
}


def _refuse_removed_paths(saved: dict, want: dict) -> None:
    """Drop the removed paths' entries from a saved fingerprint, and
    refuse a checkpoint that one of them wrote: each summed in its own
    order, so no exact resume exists.  A missing entry predates its
    knob and is not checked (the level_dims precedent), except that
    before the combine and its KD were recorded only the scatter combine
    existed."""
    if ("combine_kd" not in saved
            and want.get("combine_kd", "n/a") != "n/a"):
        saved["combine"] = "scatter"
    if saved.get("combine") == "scatter" and not saved.get("level_dims"):
        # no level grids: the scatter combine looped over nothing
        saved["combine"] = "n/a"
    for key, (path, kept) in _REMOVED_PATHS.items():
        value = saved.pop(key, kept[0])
        if value not in kept:
            raise ValueError(
                f"checkpoint was written by {path} ({key}={value!r}), "
                f"which this program no longer has; it cannot resume "
                f"exactly")


def state_dict(solver) -> dict:
    """Everything needed to continue ``solve()`` after step ``t``."""
    from pcg_mpi_solver_tpu.parallel.distributed import fetch_global

    return {
        "un": fetch_global(solver.un, solver.mesh),
        "flags": np.asarray(solver.flags, dtype=np.int64),
        "relres": np.asarray(solver.relres, dtype=np.float64),
        "iters": np.asarray(solver.iters, dtype=np.int64),
        "step_times": np.asarray(solver.step_times, dtype=np.float64),
        "export_count": np.int64(getattr(solver, "_export_count", 0)),
        "export_times": np.asarray(getattr(solver, "_export_times", []),
                                   dtype=np.float64),
        "export_wall": np.float64(solver._export_wall),
        "probe_u": (np.stack(solver._probe_u)
                    if getattr(solver, "_probe_u", [])
                    else np.zeros((0, 0))),
    }


def load_state_dict(solver, state: dict) -> None:
    from pcg_mpi_solver_tpu.parallel.distributed import put_sharded

    solver.un = put_sharded(
        np.asarray(state["un"], dtype=solver.dtype),
        solver.mesh, solver._part_spec)
    solver.flags = [int(v) for v in state["flags"]]
    solver.relres = [float(v) for v in state["relres"]]
    solver.iters = [int(v) for v in state["iters"]]
    solver.step_times = [float(v) for v in state["step_times"]]
    solver._export_count = int(state["export_count"])
    solver._export_times = [float(v) for v in state["export_times"]]
    solver._export_wall = float(state.get("export_wall", 0.0))
    probe = np.asarray(state["probe_u"])
    solver._probe_u = [] if probe.size == 0 else [row for row in probe]


class CheckpointManager:
    """Writes/reads per-step solver checkpoints under one directory."""

    def __init__(self, path: str):
        self.path = path

    def _ckpt_file(self, t: int) -> str:
        return os.path.join(self.path, f"ckpt_{t:06d}.npz")

    def save(self, solver, t: int) -> str:
        """Checkpoint solver state after completed step ``t``.

        Multi-host safe: state_dict's device fetch is collective and runs on
        every process; only process 0 touches the filesystem (the analogue
        of the reference's rank-0-gated writes, file_operations.py:348-396)."""
        payload = dict(state_dict(solver))
        from pcg_mpi_solver_tpu.utils.io import is_primary

        out = self._ckpt_file(t)
        if not is_primary():
            return out
        os.makedirs(self.path, exist_ok=True)
        tmp = out + ".tmp"
        payload["t"] = np.int64(t)
        payload["fingerprint"] = np.frombuffer(
            json.dumps(_fingerprint(solver), sort_keys=True).encode(),
            dtype=np.uint8).copy()
        with open(tmp, "wb") as f:
            np.savez_compressed(f, **payload)
        os.replace(tmp, out)
        ptr = os.path.join(self.path, "latest")
        with open(ptr + ".tmp", "w") as f:
            f.write(os.path.basename(out))
        os.replace(ptr + ".tmp", ptr)
        return out

    @staticmethod
    def _valid_step(path: str) -> Optional[int]:
        """The step index of a readable checkpoint file, else None (a
        truncated/corrupt npz — e.g. the write was killed before the
        atomic publish discipline existed, or the disk filled — must
        read as absent, not crash the resume)."""
        try:
            with np.load(path) as z:
                return int(z["t"])
        except Exception:                               # noqa: BLE001
            return None

    def latest_step(self) -> Optional[int]:
        """Newest restorable step: the ``latest`` pointer's target when
        it exists and loads, else the newest VALID ``ckpt_*.npz`` in the
        directory — a dangling/corrupt pointer target costs one
        checkpoint interval, not the whole resume."""
        candidates = []
        ptr = os.path.join(self.path, "latest")
        ptr_name = None
        if os.path.exists(ptr):
            with open(ptr) as f:
                ptr_name = f.read().strip()
            candidates.append(ptr_name)
        candidates += sorted(
            (os.path.basename(p) for p in
             _glob.glob(os.path.join(self.path, "ckpt_*.npz"))
             if os.path.basename(p) != ptr_name),
            reverse=True)
        for name in candidates:
            p = os.path.join(self.path, name)
            if not os.path.exists(p):
                continue
            t = self._valid_step(p)
            if t is None:
                continue
            if name != ptr_name and ptr_name is not None:
                warnings.warn(
                    f"checkpoint 'latest' pointer references "
                    f"{ptr_name!r} (missing or corrupt); falling back "
                    f"to {name!r}")
            return t
        return None

    def restore(self, solver, t: Optional[int] = None, *,
                elastic: bool = False,
                recorder=None) -> Optional[int]:
        """Load the checkpoint for step ``t`` (default: latest) into
        ``solver``.  Returns the restored step index, or None when no
        checkpoint exists.  Raises on fingerprint mismatch — except a
        mismatch confined to ``n_procs`` under ``elastic=True``: step
        checkpoints hold the globally-fetched state, so restoring onto
        a different process count is exact, and the NAMED elastic path
        records an ``elastic_resume`` event instead of refusing."""
        if t is None:
            t = self.latest_step()
            if t is None:
                return None
        with np.load(self._ckpt_file(t)) as z:
            saved = json.loads(bytes(z["fingerprint"]).decode())
            # Checkpoints written before the precond field existed can only
            # have come from the scalar-Jacobi path.
            saved.setdefault("precond", "jacobi")
            # Checkpoints written before the mg_shape field existed can
            # only have come from a non-mg preconditioner.
            saved.setdefault("mg_shape", "n/a")
            # Checkpoints written before the pcg_variant field existed
            # can only have come from the classic loop.
            saved.setdefault("pcg_variant", "classic")
            # Checkpoints written before the nrhs field existed can only
            # have come from the single-RHS paths.
            saved.setdefault("nrhs", 1)
            want = _fingerprint(solver)
            # Checkpoints that predate the level-dims field did not
            # record which layout produced them (block tiling existed
            # briefly before the field did), so the historical value is
            # unknowable — skip the check rather than guess.
            saved.setdefault("level_dims", want["level_dims"])
            _refuse_removed_paths(saved, want)
            saved.setdefault("combine_kd", want["combine_kd"])
            # pre-f64_refresh checkpoints can only have come from the
            # stencil formulation (the general form did not exist)
            saved.setdefault("f64_refresh", "stencil")
            # Checkpoints written before the fingerprint-completeness
            # sweep (analysis/) did not record the remaining numerics
            # knobs although the knobs themselves already existed —
            # their historical values are unknowable, so skip the new
            # checks for legacy checkpoints rather than guess (the
            # level_dims precedent above).
            for k in ("dot_dtype", "max_stag_steps", "inner_tol",
                      "mixed_knobs", "trace_len", "n_procs"):
                saved.setdefault(k, want[k])
            if saved != want:
                diffs = {k: (saved.get(k), want[k]) for k in want
                         if saved.get(k) != want[k]}
                if elastic and set(diffs) == {"n_procs"}:
                    if recorder is not None:
                        recorder.event(
                            "elastic_resume",
                            from_procs=int(saved.get("n_procs", -1)),
                            to_procs=int(want["n_procs"]),
                            prefix="ckpt")
                        recorder.inc("resilience.elastic_resume")
                else:
                    raise ValueError(
                        f"checkpoint/solver mismatch (saved, current): "
                        f"{diffs}")
            load_state_dict(solver, {k: z[k] for k in z.files
                                     if k not in ("t", "fingerprint")})
        return t


# ----------------------------------------------------------------------
# Mid-Krylov snapshots (resilience subsystem)
# ----------------------------------------------------------------------

def _flatten(tree: Dict[str, Any], prefix: str = "") -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for k, v in tree.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_flatten(v, key + "/"))
        else:
            out[key] = np.asarray(v)
    return out


def _unflatten(flat: Dict[str, Any]) -> Dict[str, Any]:
    out: Dict[str, Any] = {}
    for key, v in flat.items():
        parts = key.split("/")
        d = out
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = v
    return out


class SnapshotStore:
    """Mid-solve (intra-step) Krylov snapshots under the checkpoint dir.

    One ``snap_{t:06d}.npz`` per in-flight step, published through
    ``utils/io.write_atomic`` (readers never see a half-write — exactly
    the failure window a snapshot exists to survive) and guarded by the
    same solver fingerprint as the step checkpoints: resuming a Krylov
    carry against different numerics would silently produce garbage.
    The payload is an arbitrary numpy pytree (the chunked engine's
    resumable state — direct-mode carry or mixed-mode outer-cycle
    state) flattened with ``/``-joined keys.

    The record is a mid-STEP artifact on the quasi-static path: the
    owning step deletes it on completion (:meth:`discard`), so a later
    resume can never replay a snapshot past the state it belongs to.
    The time-history drivers (dynamics/Newmark) reuse the store with
    ``prefix="step"`` for their timestep-granular checkpoints
    (``step_*.npz``), where records deliberately outlive their step —
    they are the resume points — and on-disk retention is bounded
    instead: after each successful write only the newest K files of the
    store's prefix are kept (``PCG_TPU_SNAP_KEEP``, default 2), so a
    week-long time history cannot fill the disk.
    """

    def __init__(self, path: str, fingerprint: Optional[dict] = None,
                 prefix: str = "snap"):
        self.path = path
        self.fingerprint = fingerprint
        self.prefix = prefix

    @classmethod
    def for_solver(cls, solver) -> "SnapshotStore":
        return cls(solver.config.checkpoint_path, _fingerprint(solver))

    @classmethod
    def for_many_solver(cls, solver, nrhs: int,
                        rhs_hash: str = "") -> "SnapshotStore":
        """Blocked-solve store (``Solver.solve_many``): same fingerprint
        guard with the ACTUAL block width AND a content hash of the rhs
        block, distinct ``many_*.npz`` namespace.  Resuming a width-R
        blocked carry under a width-R' request — or under a same-width
        block of DIFFERENT load cases (the scalar paths derive their rhs
        from the fingerprinted model/schedule; solve_many's rhs is a
        per-request input, so it must be fingerprinted itself) — fails
        as a clear mismatch naming the field, never as a silently-wrong
        Krylov continuation or a shape error deep in the dispatch."""
        fp = dict(_fingerprint(solver))
        fp["nrhs"] = int(nrhs)
        fp["rhs_hash"] = str(rhs_hash)
        # whether the blocked cycle programs carry the fallback-
        # preconditioner operand (driver._many_use_fb): a carry whose
        # ``prec_sel`` flipped a column to the fallback must never
        # resume into a program compiled without one — the selection
        # would be silently compiled out
        fp["many_fallback"] = bool(
            getattr(solver, "_many_use_fb", lambda: False)())
        return cls(solver.config.checkpoint_path, fp, prefix="many")

    @classmethod
    def for_time_solver(cls, solver) -> "SnapshotStore":
        """Timestep-granular store for the dynamics/Newmark drivers:
        same fingerprint guard, distinct ``step_*.npz`` namespace so a
        quasi-static mid-Krylov snapshot in the same checkpoint dir can
        never be mistaken for a completed-timestep state."""
        return cls(solver.config.checkpoint_path, _fingerprint(solver),
                   prefix="step")

    def _file(self, t: int) -> str:
        return os.path.join(self.path, f"{self.prefix}_{t:06d}.npz")

    @staticmethod
    def retention() -> int:
        """On-disk retention bound: keep the newest K files per prefix
        (``PCG_TPU_SNAP_KEEP``, default 2 — the newest plus one spare in
        case the newest write raced a kill).  A malformed value must not
        disable the bound it configures."""
        raw = os.environ.get("PCG_TPU_SNAP_KEEP", "").strip()
        if not raw:
            return 2
        try:
            k = int(raw)
        except ValueError:
            warnings.warn(f"PCG_TPU_SNAP_KEEP={raw!r} is not an integer; "
                          "keeping the default 2 snapshots")
            return 2
        return max(k, 1)

    def _prune(self) -> None:
        """Drop all but the newest K snapshots of this prefix.  Runs
        only after a successful atomic publish, so the newest file is
        always a complete record; zero-padded names sort by step.  Only
        files of THIS store's ``<prefix>_<step>.npz`` naming count:
        the epoch shards/markers a GroupSnapshotStore keeps under the
        same prefix (``<prefix>_e<E>.p<idx>.npz``) have their own
        committed-epoch retention, and a per-file prune racing across
        rank shards is exactly how retention used to split a group's
        ``latest()`` resolution."""
        files = sorted(
            p for p in _glob.glob(
                os.path.join(self.path, f"{self.prefix}_*.npz"))
            if os.path.basename(p)[len(self.prefix) + 1:-4].isdigit())
        for p in files[:-self.retention()]:
            try:
                os.remove(p)
            except OSError:
                pass        # a racing reader/cleaner already has it

    def latest(self) -> Optional[int]:
        """Newest restorable step index of this prefix, or None.  A
        corrupt/truncated newest file costs one retention slot, not the
        resume (same posture as CheckpointManager.latest_step)."""
        steps = []
        for p in _glob.glob(os.path.join(self.path,
                                         f"{self.prefix}_*.npz")):
            stem = os.path.basename(p)[len(self.prefix) + 1:-4]
            try:
                steps.append(int(stem))
            except ValueError:
                continue
        for t in sorted(steps, reverse=True):
            try:
                with np.load(self._file(t)) as z:
                    if "__t" in z.files:
                        return t
            except Exception:                           # noqa: BLE001
                continue        # corrupt reads as absent; older file next
        return None

    def save(self, t: int, state: Dict[str, Any]) -> str:
        """Persist the (host numpy) state pytree for in-flight step
        ``t``.  Multi-host safe like :meth:`CheckpointManager.save`: the
        caller's state fetch is collective, only process 0 writes."""
        from pcg_mpi_solver_tpu.utils.io import is_primary, write_atomic

        out = self._file(t)
        if not is_primary():
            return out
        os.makedirs(self.path, exist_ok=True)
        flat = _flatten(state)
        flat["__t"] = np.int64(t)
        flat["__fingerprint"] = np.frombuffer(
            json.dumps(self.fingerprint or {}, sort_keys=True).encode(),
            dtype=np.uint8).copy()
        write_atomic(out, lambda f: np.savez_compressed(f, **flat))
        self._prune()
        return out

    def load(self, t: int) -> Optional[Dict[str, Any]]:
        """The state pytree snapshotted inside step ``t``, or None.
        Raises on a fingerprint mismatch (resuming a carry under drifted
        numerics must fail loudly, like the step checkpoints); a
        corrupt/truncated snapshot reads as absent — the step then
        simply restarts cold from its start state."""
        path = self._file(t)
        if not os.path.exists(path):
            return None
        try:
            with np.load(path) as z:
                flat = {k: z[k] for k in z.files}
            # a structurally-loadable npz with a missing/garbled
            # fingerprint entry is just as corrupt as a torn zip — same
            # reads-as-absent outcome, not a KeyError mid-resume
            saved = json.loads(bytes(flat.pop("__fingerprint")).decode())
        except Exception as e:                          # noqa: BLE001
            warnings.warn(f"mid-solve snapshot {path} unreadable "
                          f"({type(e).__name__}: {e}); restarting the "
                          "step from its start state")
            return None
        flat.pop("__t", None)
        self._reconcile_fingerprint(saved)
        return _unflatten(flat)

    def _reconcile_fingerprint(self, saved: dict) -> None:
        """Apply the legacy shims to a record's saved fingerprint, then
        compare against this store's live fingerprint, dispatching any
        mismatch to :meth:`_fingerprint_mismatch` (shared by the base
        per-file reads and the epoch-shard joins of
        ``resilience.distributed.GroupSnapshotStore``)."""
        if self.fingerprint is None:
            return
        if "level_dims" in self.fingerprint:
            _refuse_removed_paths(saved, self.fingerprint)
        # snapshots written before the nrhs field existed can only have
        # come from the width-1 scalar paths (same back-compat shim as
        # CheckpointManager.restore — without it every pre-existing
        # snap_*/step_* resume point would mismatch on upgrade).  Only
        # when THIS store's fingerprint carries the field: a custom
        # fingerprint without it must keep comparing equal to itself.
        if "nrhs" in self.fingerprint:
            saved.setdefault("nrhs", 1)
        if "many_fallback" in self.fingerprint:
            # blocked snapshots written before the per-column fallback
            # wiring existed can only have come from programs without
            # the fallback operand
            saved.setdefault("many_fallback", False)
        if "mg_shape" in self.fingerprint:
            # snapshots written before the mg_shape field existed can
            # only have come from a non-mg preconditioner — resuming
            # them under precond='mg' still mismatches (on precond AND
            # on "n/a" != the live shape), loudly
            saved.setdefault("mg_shape", "n/a")
        # snapshots written before the fingerprint-completeness sweep
        # (analysis/) did not record these numerics knobs; their
        # historical values are unknowable — skip the new checks for
        # legacy snapshots rather than guess (same rationale and guard
        # as the nrhs shim above)
        for k in ("dot_dtype", "max_stag_steps", "inner_tol",
                  "mixed_knobs", "trace_len", "n_procs"):
            if k in self.fingerprint:
                saved.setdefault(k, self.fingerprint[k])
        if saved != self.fingerprint:
            diffs = {k: (saved.get(k), self.fingerprint[k])
                     for k in self.fingerprint
                     if saved.get(k) != self.fingerprint[k]}
            self._fingerprint_mismatch(saved, diffs)

    def _fingerprint_mismatch(self, saved: dict, diffs: dict) -> None:
        """Mismatch outcome hook: the base store always refuses; the
        group store's elastic path tolerates an ``n_procs``-only diff
        as a named event."""
        raise ValueError(
            f"mid-solve snapshot/solver mismatch (saved, current): "
            f"{diffs}")

    def discard(self, t: int) -> None:
        from pcg_mpi_solver_tpu.utils.io import is_primary

        if not is_primary():
            return
        try:
            os.remove(self._file(t))
        except OSError:
            pass
