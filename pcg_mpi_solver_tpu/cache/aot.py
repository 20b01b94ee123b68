"""Ahead-of-time compile path: persistent XLA cache + jax.export'd steps.

* ``use_compilation_cache`` places jax's persistent compilation cache:
  ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (jax reads
  it itself; nothing is set in code), otherwise ``<repo>/.jax_cache``.
  A fixed path, because the path is part of what a later run must find.
* ``export_step``/``store_step``/``load_step`` serialize the jitted PCG
  step via ``jax.export`` keyed by its ABSTRACT signature (shapes /
  dtypes / shardings) under ``RunConfig.cache_dir``: a warm session
  deserializes StableHLO instead of re-tracing the solver's Python, so a
  same-shape-class re-run skips tracing entirely and its
  (deserialized-module) compile hits the persistent XLA cache.

Import contract: jax is imported lazily inside functions — this module
may be imported before the accelerator environment is configured.
"""

from __future__ import annotations

import os
import time
from typing import Optional

_REPO_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compilation_cache() -> str:
    """Place jax's persistent compilation cache; returns its directory.
    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured by jax itself and
    nothing is set here; otherwise the cache goes to ``<repo>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    d = os.path.join(_REPO_DIR, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", d)
    return d


def _entry_path(cache_dir: str, key: str) -> str:
    return os.path.join(cache_dir, "aot", f"{key}.jaxexport")


def abstract_like(tree):
    """Concrete (committed) array pytree -> ShapeDtypeStruct pytree with
    the SAME shardings, for sharding-faithful .lower()/export calls."""
    import jax

    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=x.sharding), tree)


def signature_repr(abstract_args) -> str:
    """Stable repr of an abstract signature (shapes/dtypes/shardings) for
    key derivation."""
    import jax

    leaves, treedef = jax.tree.flatten(abstract_args)
    parts = [f"{tuple(l.shape)}:{l.dtype}:"
             f"{getattr(l, 'sharding', None)}" for l in leaves]
    return f"{treedef}|" + ";".join(parts)


def export_step(jit_fn, abstract_args):
    """Trace + lower ``jit_fn`` at the abstract signature and return the
    serializable ``jax.export.Exported``.  The one trace this costs on a
    COLD run is what every warm run skips."""
    from jax import export as jexport

    return jexport.export(jit_fn)(*abstract_args)


def load_step(cache_dir: str, key: str, recorder=None):
    """Deserialize the exported step for ``key``; None on miss.

    Corrupt, truncated, or version-incompatible blobs (jax.export
    enforces its own calling-convention versioning; a killed writer
    predating the atomic-publish discipline, or a torn disk, leaves
    truncated ones) are QUARANTINED — renamed to ``<entry>.corrupt``,
    overwriting any previous quarantine for the key so at most one is
    kept — and treated as a cache miss, exactly matching the
    corruption handling ``cache/partition_cache.load_partition``
    already has (there the entry is removed; here the blob is kept for
    forensics since a bad AOT entry usually means a toolchain-version
    skew worth diagnosing).  The caller then re-exports and the fresh
    entry replaces the bad one: a corrupt cache can cost one re-trace,
    never a failed solve."""
    path = _entry_path(cache_dir, key)
    if not os.path.exists(path):
        return None
    from jax import export as jexport

    try:
        with open(path, "rb") as f:
            exported = jexport.deserialize(bytearray(f.read()))
    except Exception as e:                              # noqa: BLE001
        try:
            os.replace(path, path + ".corrupt")
            action = "quarantined"
        except OSError:
            try:
                os.remove(path)
            except OSError:
                pass
            action = "removed"
        if recorder is not None:
            recorder.inc("cache.aot.corrupt")
            recorder.event("cache", name="aot.step.corrupt", hit=False,
                           key=key, wall_s=0.0, action=action,
                           error=f"{type(e).__name__}: {e}")
        return None
    try:
        os.utime(path)                                  # LRU touch
    except OSError:
        pass
    return exported


def store_step(cache_dir: str, key: str, exported) -> bool:
    """Atomically publish a serialized exported step; best-effort.  The
    half-written tmp of a failed write is removed, and the aot dir is
    LRU-evicted to the same PCG_TPU_CACHE_GB cap as the partition
    entries (code/version re-keys orphan old generations here too)."""
    from pcg_mpi_solver_tpu.cache.partition_cache import evict_lru
    from pcg_mpi_solver_tpu.utils.io import write_atomic

    path = _entry_path(cache_dir, key)
    try:
        blob = bytes(exported.serialize())
        os.makedirs(os.path.dirname(path), exist_ok=True)
        write_atomic(path, blob)
    except Exception:                                   # noqa: BLE001
        return False
    evict_lru(os.path.dirname(path), keep=path,
              suffix=".jaxexport")
    # quarantined corrupt blobs (load_step) are forensics, not cache
    # entries — they get the same LRU discipline under their own suffix
    # so they can never grow the shared dir unboundedly (every version
    # bump re-keys entries, so per-key overwrite alone does not bound
    # them)
    evict_lru(os.path.dirname(path), keep=path,
              suffix=".jaxexport.corrupt")
    return True


def cached_step(cache_dir: str, key: str, jit_fn, abstract_args,
                recorder=None) -> Optional[object]:
    """Load-or-export the step program; returns the ``Exported`` (from
    disk on a hit — zero tracing — or freshly exported on a miss), or
    None when export is unsupported for this program/jax version (the
    caller keeps its plain jit).  Cold/warm attribution mirrors
    ``cached_partition``."""
    t0 = time.perf_counter()
    exported = load_step(cache_dir, key, recorder=recorder)
    if exported is not None:
        if recorder is not None:
            recorder.inc("cache.aot.hit")
            recorder.event("cache", name="aot.step", hit=True, key=key,
                           wall_s=round(time.perf_counter() - t0, 6))
        return exported
    try:
        exported = export_step(jit_fn, abstract_args)
        stored = store_step(cache_dir, key, exported)
        err = None
    except Exception as e:                              # noqa: BLE001
        exported, stored = None, False
        err = f"{type(e).__name__}: {e}"
    if recorder is not None:
        recorder.inc("cache.aot.miss" if err is None
                     else "cache.aot.unsupported")
        recorder.event("cache", name="aot.step", hit=False, key=key,
                       stored=stored, error=err,
                       wall_s=round(time.perf_counter() - t0, 6))
    return exported
