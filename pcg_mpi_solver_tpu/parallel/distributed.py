"""Multi-host (DCN) support.

The reference scales across nodes with mpi4py over OpenMPI — tagged p2p
halo messages plus allreduces (reference: SURVEY.md §2d; pcg_solver.py:
317-334, 622-628).  The TPU-native equivalent has no user-level messaging:
``jax.distributed`` forms one multi-controller program, the device mesh
spans all hosts (ICI within a slice, DCN across), and the SAME compiled
solve program runs everywhere — XLA routes the psum/collectives.

What this module provides:

- :func:`init_distributed` — process bootstrap (coordinator discovery from
  standard env vars, explicit args, or single-process no-op).
- :func:`make_global_mesh` — 1-D parts mesh over every device of every host.
- :func:`put_sharded` / :func:`put_tree` — build sharded global device
  arrays from host numpy data; on multi-host each process materializes only
  its addressable shards (the analogue of the reference's per-rank partition
  pickles + shared-memory staging, file_operations.py:306-339).

Single-process semantics are identical to plain ``device_put``, so every
code path here is exercised by the single-host test suite; multi-host adds
only the bootstrap.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np

from pcg_mpi_solver_tpu.parallel.mesh import PARTS_AXIS


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> int:
    """Initialize jax.distributed for a multi-host run; returns process id.

    Resolution order: explicit args > env vars (``PCG_TPU_COORDINATOR`` /
    ``PCG_TPU_NUM_PROCS`` / ``PCG_TPU_PROC_ID``, mirroring the standard JAX
    ones) > single-process no-op.  Safe to call repeatedly.
    """
    coordinator_address = coordinator_address or os.environ.get("PCG_TPU_COORDINATOR")
    if num_processes is None and os.environ.get("PCG_TPU_NUM_PROCS"):
        num_processes = int(os.environ["PCG_TPU_NUM_PROCS"])
    if process_id is None and os.environ.get("PCG_TPU_PROC_ID"):
        process_id = int(os.environ["PCG_TPU_PROC_ID"])

    if coordinator_address is None and num_processes is None:
        return jax.process_index()          # single process / TPU pod auto-init
    global _initialized
    if not _initialized:
        # NOTE: must run before anything touches the XLA backend — do not
        # query jax.process_count() here.
        if "cpu" in os.environ.get("JAX_PLATFORMS", ""):
            # Multi-process CPU groups (the weak-scaling setup ladder,
            # the 2/4-process tests) need a cross-process collectives
            # implementation — the default CPU client rejects
            # multiprocess computations outright ("Multiprocess
            # computations aren't implemented on the CPU backend").
            # Gloo ships with jaxlib; best-effort for jax versions
            # without the knob.
            try:
                jax.config.update("jax_cpu_collectives_implementation",
                                  "gloo")
            except Exception:               # noqa: BLE001
                pass
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        _initialized = True
    return jax.process_index()


_initialized = False


def make_global_mesh(n_devices: Optional[int] = None) -> jax.sharding.Mesh:
    """1-D ``(parts,)`` mesh over all devices of all processes (DCN-aware:
    jax.devices() enumerates host-local devices first, so contiguous part
    blocks land host-local and halo traffic prefers ICI)."""
    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    return jax.sharding.Mesh(np.asarray(devices), (PARTS_AXIS,))


def put_sharded(x: np.ndarray, mesh: jax.sharding.Mesh,
                spec: jax.sharding.PartitionSpec) -> jax.Array:
    """Host numpy -> sharded global device array.

    Single-process: plain device_put.  Multi-process: each process builds
    only its addressable shards via make_array_from_callback (every process
    must hold the rows its devices own; the part-major layout makes that a
    contiguous block of the leading axis)."""
    sharding = jax.sharding.NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    return jax.make_array_from_callback(
        np.shape(x), sharding, lambda idx: np.asarray(x[idx]))


def gather_owned_global(pm, x, mesh: Optional[jax.sharding.Mesh] = None,
                        dtype=None) -> np.ndarray:
    """(P, n_loc[, R]) part-padded dof vector/block -> (glob_n_dof[, R])
    global array via the owner mask (each dof written by exactly one
    part).  The one shared mask-and-scatter idiom for every solver's
    global views — a trailing RHS-block axis rides through unchanged
    (one fetch, one masked scatter for the whole block)."""
    return scatter_owned_global(pm, fetch_global(x, mesh), dtype)


def scatter_owned_global(pm, xh: np.ndarray, dtype=None) -> np.ndarray:
    """The host half of :func:`gather_owned_global`: an already-fetched
    (P, n_loc[, R]) array -> (glob_n_dof[, R]) through the owner mask."""
    out = np.zeros((pm.glob_n_dof,) + xh.shape[2:], dtype=dtype or np.float64)
    m = (pm.weight > 0) & (pm.dof_gid >= 0)
    out[pm.dof_gid[m]] = xh[m]
    return out


def fetch_global(x, mesh: Optional[jax.sharding.Mesh] = None) -> np.ndarray:
    """Fetch a (possibly multi-host sharded) jax.Array as full host numpy.

    Single-process (or fully addressable) arrays are a plain device_get; a
    multi-host sharded array is first resharded to fully-replicated (an XLA
    all-gather over DCN) so every process can read the whole value — the
    analogue of the reference's Comm.gather-to-rank-0 exports
    (pcg_solver.py:910-911)."""
    if not isinstance(x, jax.Array) or x.is_fully_addressable:
        return np.asarray(x)
    if mesh is None:
        mesh = jax.sharding.Mesh(
            np.asarray(x.sharding.mesh.devices), x.sharding.mesh.axis_names)
    rep = jax.jit(lambda a: a,
                  out_shardings=jax.sharding.NamedSharding(
                      mesh, jax.sharding.PartitionSpec()))(x)
    return np.asarray(rep)


def fetch_addressable(x) -> tuple:
    """Fetch only this process's addressable rows of a parts-sharded array.

    Returns ``(rows, p0, p1)`` with ``rows == x[p0:p1]``.  The collective-free
    counterpart of :func:`fetch_global` — the basis of parallel result
    writes (each process persists its own contiguous part block, the
    analogue of the reference's MPI-IO shared-file writes at computed
    offsets, file_operations.py:348-396)."""
    if not isinstance(x, jax.Array) or x.is_fully_addressable:
        a = np.asarray(x)
        return a, 0, a.shape[0]
    shards = sorted(x.addressable_shards,
                    key=lambda s: s.index[0].start or 0)
    rows = np.concatenate([np.asarray(s.data) for s in shards], axis=0)
    p0 = shards[0].index[0].start or 0
    p1 = p0 + rows.shape[0]
    # The part-range labeling is only valid if this process's shards tile
    # [p0, p1) contiguously — true for make_global_mesh's device order,
    # not necessarily for an arbitrary (e.g. torus-reordered) mesh.
    ends = [s.index[0] for s in shards]
    cov = sorted((sl.start or 0, sl.stop) for sl in ends)
    pos = p0
    for a, b in cov:
        if a != pos:
            raise ValueError(
                f"addressable shards are not part-contiguous: {cov} "
                "(use make_global_mesh, or export via fetch_global)")
        pos = b
    return rows, p0, p1


class HostComm:
    """Host-side reduction group over the processes of a jax.distributed
    run — the multi-process implementation of the sharded-setup exchange
    protocol (``parallel/partition.SerialComm`` is the 1-process twin).
    Built on ``multihost_utils.process_allgather`` + a numpy reduce, so
    arbitrary host arrays (the partition layout's count/owner vectors)
    ride the existing collective fabric; every process must call
    ``allreduce`` in the same order with same-shaped arrays."""

    _OPS = {"sum": np.sum, "min": np.min, "max": np.max}

    def __init__(self):
        self.n_procs = jax.process_count()

    def allreduce(self, arr: np.ndarray, op: str) -> np.ndarray:
        arr = np.asarray(arr)
        if self.n_procs == 1:
            return arr
        from jax.experimental import multihost_utils

        gathered = multihost_utils.process_allgather(arr)
        return self._OPS[op](np.asarray(gathered), axis=0).astype(arr.dtype)

    def warmup(self, sizes=(1,)) -> None:
        """Pay the one-time collective-fabric costs (gloo/ICI channel
        setup, the per-shape allgather program compile) BEFORE any timed
        partition span — connection establishment and program compile
        are not partition work and must not pollute
        ``partition_build_s``.  ``sizes``: the exact 1-D payload sizes
        the exchange will use (``parallel/partition.
        layout_exchange_sizes``); every process must call with the same
        sequence (each warmup is itself a collective).  Routed through
        ``allreduce_groups`` so the warmed program matches the packed
        (int32) path the real exchange takes."""
        for n in sizes:
            self.allreduce_groups([([np.zeros(int(n), np.int64)], "max")])

    def allreduce_many(self, arrs, op: str):
        """Several same-op reductions in ONE collective (see
        ``allreduce_groups`` — this is the single-group case)."""
        return self.allreduce_groups([(arrs, op)])[0]

    def allreduce_groups(self, groups):
        """Differently-reduced array groups in ONE collective: an
        allreduce is an allgather + a local reduce, so every group
        shares a single packed buffer — one dispatch, one per-shape
        program, one gloo/DCN round for the whole layout exchange.
        ALWAYS packed as int32 (halves the wire payload; every
        layout-exchange value — counts, owners, per-part sizes — fits
        by design): the dtype choice must be identical on every process
        (a per-process int64 fallback would enter the collective with
        mismatched byte-widths), so an out-of-range value raises LOUDLY
        here instead."""
        from jax.experimental import multihost_utils

        groups = [([np.asarray(a) for a in arrs], op)
                  for arrs, op in groups]
        if self.n_procs == 1:
            return [arrs for arrs, _ in groups]
        flats = [a.astype(np.int64).ravel()
                 for arrs, _ in groups for a in arrs]
        flat = (np.concatenate(flats) if flats
                else np.zeros(0, np.int64))
        if flat.size and (int(flat.max()) > 2 ** 31 - 1
                          or int(flat.min()) < -(2 ** 31)):
            raise OverflowError(
                "HostComm.allreduce_groups: a layout-exchange value "
                "exceeds int32 — the packed exchange protocol assumes "
                "counts/owners/per-part sizes below 2^31 (a single part "
                "beyond that is outside the design envelope); widen the "
                "protocol deliberately rather than per-process")
        flat = flat.astype(np.int32)
        if flat.size <= self.CHUNK:
            gathered = np.asarray(
                multihost_utils.process_allgather(flat)).astype(np.int64)
            red_flat = None
        else:
            # Chunked gather-reduce: one (n_procs, N) copy of an
            # O(n_dof) payload would multiply the very memory bound the
            # sharded setup exists to hold — reduce chunk by chunk so
            # the transient stays n_procs * CHUNK regardless of model
            # size.  Every chunk is padded to the SAME length, so the
            # whole loop reuses one compiled allgather program (padding
            # is sliced off before the reduce; all processes iterate
            # the identical chunk sequence).
            red_flat = np.empty(flat.size, np.int64)
            pos_c = 0
            while pos_c < flat.size:
                n = min(self.CHUNK, flat.size - pos_c)
                buf = np.zeros(self.CHUNK, np.int32)
                buf[:n] = flat[pos_c:pos_c + n]
                g = np.asarray(multihost_utils.process_allgather(buf))
                # per-position op: resolve below per group segment —
                # store BOTH reductions? No: segments are contiguous,
                # so reduce lazily per segment from the gathered chunk.
                # To keep one pass, stash the raw chunk reductions for
                # both ops only when a segment boundary crosses the
                # chunk; simpler and still bounded: keep the gathered
                # chunk and reduce the overlapping segments now.
                for seg_pos, seg_n, op in self._segments(groups):
                    lo = max(seg_pos, pos_c)
                    hi = min(seg_pos + seg_n, pos_c + n)
                    if lo < hi:
                        red_flat[lo:hi] = self._OPS[op](
                            g[:, lo - pos_c:hi - pos_c], axis=0)
                pos_c += n
        out, pos = [], 0
        for arrs, op in groups:
            red_arrs = []
            for a in arrs:
                n = int(a.size)
                if red_flat is not None:
                    red = red_flat[pos:pos + n]
                else:
                    red = self._OPS[op](gathered[:, pos:pos + n], axis=0)
                red_arrs.append(red.reshape(a.shape).astype(a.dtype))
                pos += n
            out.append(red_arrs)
        return out

    #: chunk length (int32 entries) of the chunked gather-reduce path:
    #: 4M entries = 16 MB per process-copy per chunk
    CHUNK = 1 << 22

    @staticmethod
    def _segments(groups):
        """(pos, size, op) spans of the packed buffer, one per array."""
        pos = 0
        for arrs, op in groups:
            for a in arrs:
                n = int(np.asarray(a).size)
                yield pos, n, op
                pos += n


def local_part_range(mesh: jax.sharding.Mesh, n_parts: int):
    """The contiguous [lo, hi) part range whose rows are addressable by
    THIS process on a parts-sharded (P, ...) array over ``mesh``, or
    None when this process's parts are not one contiguous block (an
    exotic device order — the sharded setup path then falls back to the
    full build).  Single process: the full range."""
    if jax.process_count() == 1:
        return (0, n_parts)
    devices = list(mesh.devices.flat)
    if n_parts % len(devices) != 0:
        return None
    ppd = n_parts // len(devices)
    pid = jax.process_index()
    mine = [p for p, d in enumerate(devices) if d.process_index == pid]
    if not mine or mine != list(range(mine[0], mine[-1] + 1)):
        return None
    return (mine[0] * ppd, (mine[-1] + 1) * ppd)


def put_tree(tree, mesh: jax.sharding.Mesh, specs):
    """put_sharded over a pytree of host arrays with a matching pytree of
    specs (None leaves pass through, as with device_put).

    Each distinct array is placed once: a leaf that sits in several
    places of the tree (the index arrays the mixed f32 shadow shares with
    the f64 tree) is one device array in all of them.  Single-process,
    the leaves go in one batched ``device_put``, each part's rows from
    the host straight to the device that owns them."""
    index, leaves, shardings = {}, [], []

    def collect(t, s):
        if t is None:
            return
        if isinstance(t, dict):
            for k, v in t.items():
                collect(v, s[k])
        elif isinstance(t, (list, tuple)):
            for i, v in enumerate(t):
                collect(v, s[i])
        elif id(t) not in index:
            index[id(t)] = len(leaves)
            leaves.append(t)
            shardings.append(s)

    collect(tree, specs)
    if jax.process_count() == 1:
        placed = jax.device_put(
            leaves, [jax.sharding.NamedSharding(mesh, s) for s in shardings])
    else:
        placed = [put_sharded(np.asarray(t), mesh, s)
                  for t, s in zip(leaves, shardings)]

    def rebuild(t):
        if t is None:
            return None
        if isinstance(t, dict):
            return {k: rebuild(v) for k, v in t.items()}
        if isinstance(t, (list, tuple)):
            return type(t)(rebuild(v) for v in t)
        return placed[index[id(t)]]

    return rebuild(tree)
