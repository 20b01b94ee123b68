"""Content-addressed cache keys for the warm-path subsystem.

A cache entry is valid iff EVERYTHING that shaped its content hashes the
same: the model bundle, the partition/solver knobs, and the code
generation that produced it.  The last part is covered by embedding
``CACHE_SCHEMA`` (bumped on any serialization-layout change in cache/)
and the package version in every key — a version bump invalidates the
whole cache rather than risking a stale entry deserialized into new code.

Import contract: jax-free at module load (numpy/hashlib only).  The CLI
and bench consult keys before the accelerator environment is configured.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Any, Dict, Optional

import numpy as np

from pcg_mpi_solver_tpu import __version__
from pcg_mpi_solver_tpu.config import PCG_VARIANTS

# Bump on ANY change to what cache entries contain or how they are
# serialized (partition pickle layout, AOT export calling convention
# expectations, key payload shape).  Additive key fields need no bump —
# they change the key hash by themselves.
# 2: ISSUE 9 — the blocked (pcg_many) and fused loop bodies gained
#    per-column recovery / drift-guard carry leaves and the
#    quarantine-flag finalize; AOT entries exported from the old
#    programs must not be deserialized into the new semantics.
# 3: ISSUE 14 — PartitionedModel gained the layout/part_range fields and
#    the partition cache became shard-addressed (glue + per-part
#    entries, cache/shards.py); monolithic entries pickled by older code
#    lack the new fields and must re-key rather than deserialize.
# 4: the hybrid CombineMaps gained the transition-row segment, the
#    node-minor (P, KD, N) layout and the hpos add-back map; a cached
#    hybrid partition holding the old maps must not reach the new matvec.
# 5: the structured gse stencil runs on the flattened (y, z) node plane
#    and its device ``ck`` is the flat cell plane; an AOT step program
#    exported for the 5D stencil must not be replayed.
# 6: the hybrid CombineMaps' heavy residual became banded (flat
#    (P, sum(bands)) slot ids over the heavy nodes sorted by count, and
#    the bands' lengths); a cached partition holding the rectangular
#    (P, KE, H) maps must not reach the banded matvec.
CACHE_SCHEMA = 6

# Monkeypatchable in tests to simulate a package-version bump without
# editing the package.
PACKAGE_VERSION = __version__


def _hash_update(h, obj: Any) -> None:
    """Deterministic recursive hash of numpy arrays / builtins /
    dataclasses (dict keys canonicalized by repr sort)."""
    if obj is None:
        h.update(b"\x00none")
    elif isinstance(obj, np.ndarray):
        a = np.ascontiguousarray(obj)
        h.update(f"nd:{a.shape}:{a.dtype}".encode())
        h.update(a.tobytes())
    elif hasattr(obj, "ids") and hasattr(obj, "vals") \
            and hasattr(obj, "fill"):
        # models/model_data.SparseVec (slab-ingest nodal restriction):
        # its CONTENT must hash — falling through to repr() would hash
        # only n/nnz/dtype, making models that differ solely in nodal
        # data (loads, coordinates) collide in the partition cache
        h.update(f"sparsevec:{len(obj)}:{obj.fill!r}".encode())
        _hash_update(h, np.asarray(obj.ids))
        _hash_update(h, np.asarray(obj.vals))
    elif isinstance(obj, (bool, int, float, str, bytes, complex,
                          np.integer, np.floating, np.bool_)):
        h.update(f"{type(obj).__name__}:{obj!r}".encode())
    elif isinstance(obj, dict):
        h.update(f"dict:{len(obj)}".encode())
        for k in sorted(obj, key=repr):
            h.update(repr(k).encode())
            _hash_update(h, obj[k])
    elif isinstance(obj, (list, tuple)):
        h.update(f"seq:{len(obj)}".encode())
        for v in obj:
            _hash_update(h, v)
    elif dataclasses.is_dataclass(obj):
        h.update(type(obj).__name__.encode())
        for f in dataclasses.fields(obj):
            h.update(f.name.encode())
            _hash_update(h, getattr(obj, f.name))
    else:
        h.update(repr(obj).encode())


def model_fingerprint(model) -> str:
    """Content hash of a full ModelData bundle (every dataclass field:
    topology, loads, BCs, element library, materials, octree/grid
    metadata).  ~GB/s sha256 — sub-second even at flagship scale, and the
    ONE thing that makes the partition cache safe against silently-edited
    models (the reference's zpkl bundles carry no integrity check)."""
    h = hashlib.sha256()
    _hash_update(h, model)
    return h.hexdigest()


def array_hash(arr) -> str:
    """Short content hash of one array (e.g. an explicit elem_part map)."""
    a = np.ascontiguousarray(np.asarray(arr))
    h = hashlib.sha256(f"{a.shape}:{a.dtype}".encode())
    h.update(a.tobytes())
    return h.hexdigest()[:16]


def _digest(payload: Dict[str, Any]) -> str:
    payload = dict(payload)
    payload["cache_schema"] = CACHE_SCHEMA
    payload["version"] = PACKAGE_VERSION
    blob = json.dumps(payload, sort_keys=True, default=repr).encode()
    return hashlib.sha256(blob).hexdigest()[:32]


def partition_cache_key(model_fp: str, *, n_parts: int, backend: str,
                        dtype: str, method: str = "n/a",
                        elem_part_hash: Optional[str] = None,
                        pad_multiple: int = 8,
                        extra: Optional[Dict[str, Any]] = None) -> str:
    """Key for one serialized partition: model content + every knob that
    shapes the partition arrays.  ``extra`` carries backend-specific knobs
    (hybrid block size / merge, native-partitioner availability for
    method='auto', ...)."""
    return _digest({
        "kind": "partition",
        "model": model_fp,
        "n_parts": int(n_parts),
        "backend": backend,
        "dtype": dtype,
        "method": method,
        "elem_part": elem_part_hash,
        "pad_multiple": int(pad_multiple),
        "extra": extra or {},
    })


def partition_shard_key(model_fp: str, *, n_parts: int, part_idx: int,
                        backend: str, dtype: str, method: str = "n/a",
                        elem_part_hash: Optional[str] = None,
                        pad_multiple: int = 8,
                        extra: Optional[Dict[str, Any]] = None) -> str:
    """Key for ONE part's rows of a shard-addressed partition entry
    (ISSUE 14): the monolithic :func:`partition_cache_key` payload plus
    the STRUCTURAL ``part_idx`` component, so N hosts each read only
    their own parts' entries on a warm start.  ``part_idx`` must bite on
    its own (proven by the analysis/ partition-key-components rule):
    two parts of one partition must never collide on one entry."""
    if not (0 <= int(part_idx) < int(n_parts)):
        raise KeyError(
            f"partition_shard_key: part_idx {part_idx} outside "
            f"[0, {n_parts})")
    return _digest({
        "kind": "partition-shard",
        "model": model_fp,
        "n_parts": int(n_parts),
        "part_idx": int(part_idx),
        "backend": backend,
        "dtype": dtype,
        "method": method,
        "elem_part": elem_part_hash,
        "pad_multiple": int(pad_multiple),
        "extra": extra or {},
    })


def partition_glue_key(model_fp: str, *, n_parts: int, backend: str,
                       dtype: str, method: str = "n/a",
                       elem_part_hash: Optional[str] = None,
                       pad_multiple: int = 8,
                       extra: Optional[Dict[str, Any]] = None) -> str:
    """Key for the GLUE entry of a shard-addressed partition: the global
    layout (PartitionLayout, scalars, shared element matrices) every
    process loads alongside its own part entries.  Same payload as the
    per-part keys minus ``part_idx`` — distinct ``kind`` so glue can
    never collide with a part entry or a legacy monolithic one."""
    return _digest({
        "kind": "partition-glue",
        "model": model_fp,
        "n_parts": int(n_parts),
        "backend": backend,
        "dtype": dtype,
        "method": method,
        "elem_part": elem_part_hash,
        "pad_multiple": int(pad_multiple),
        "extra": extra or {},
    })


def mdf_fingerprint(mdf_path: str, chunk_bytes: int = 1 << 24) -> str:
    """Content hash of an on-disk MDF bundle, STREAMED file-by-file in
    bounded chunks — the slab-ingest twin of :func:`model_fingerprint`:
    a process that never materializes the full model (models/mdf.
    read_mdf_slab) still needs the one content hash every shard key
    shares, and every process must derive the identical hash from the
    identical bundle."""
    import os

    h = hashlib.sha256()
    try:
        names = sorted(os.listdir(mdf_path))
    except OSError as e:
        raise FileNotFoundError(f"mdf_fingerprint: {mdf_path}: {e}")
    for name in names:
        p = os.path.join(mdf_path, name)
        if not os.path.isfile(p):
            continue
        h.update(f"file:{name}:{os.path.getsize(p)}".encode())
        with open(p, "rb") as f:
            while True:
                chunk = f.read(chunk_bytes)
                if not chunk:
                    break
                h.update(chunk)
    return h.hexdigest()


def step_cache_key(*, abstract: Any, mesh: Any, backend: str,
                   solver: Dict[str, Any], trace_len: int,
                   glob_n_dof_eff: int, donate: bool,
                   jax_version: str,
                   pcg_variant: str = "classic",
                   precond: str = "jacobi",
                   nrhs: int = 1,
                   extra: Optional[Dict[str, Any]] = None) -> str:
    """Key for one AOT-exported PCG step program: the ABSTRACT signature
    (shapes/dtypes/shardings repr), the mesh layout, and every scalar the
    step closure bakes in as a compile-time constant (solver config,
    effective dof count, trace ring length, donation).

    ``pcg_variant`` (SolverConfig.pcg_variant) is carried as its own
    structural component on top of the solver dict: the classic and
    fused loop bodies are different programs with different carry
    pytrees, and an AOT/compile-cache hit across variants would
    deserialize the wrong one.  ``nrhs`` is the same kind of structural
    component for the batched multi-RHS programs (solve_many): the
    blocked body's carry pytree and every vector shape differ per block
    width, so programs of different nrhs must never collide (the
    abstract signature already separates them — the explicit key field
    makes the invariant survive any signature-repr change).
    ``precond`` is the same kind of structural component (ISSUE 10):
    the mg V-cycle reshapes the loop body's preconditioner apply and
    its operand pytree, so jacobi/block3/mg programs must never collide
    even if the solver dict's serialization changes; the MG-shape knobs
    (levels/degree/dims) ride ``extra["mg"]`` from the driver."""
    if pcg_variant not in PCG_VARIANTS:
        # single-source variant discipline (config.PCG_VARIANTS): a key
        # for a variant no loop builder knows would cache a program that
        # can never be rebuilt — fail here, loudly, like the gauges and
        # the collective-budget table do
        raise KeyError(
            f"step_cache_key: unknown pcg_variant {pcg_variant!r} "
            f"(valid: {PCG_VARIANTS})")
    return _digest({
        "kind": "aot-step",
        "abstract": abstract,
        "mesh": mesh,
        "backend": backend,
        "solver": solver,
        "pcg_variant": str(pcg_variant),
        "precond": str(precond),
        "nrhs": int(nrhs),
        "trace_len": int(trace_len),
        "glob_n_dof_eff": int(glob_n_dof_eff),
        "donate": bool(donate),
        "jax": jax_version,
        "extra": extra or {},
    })
