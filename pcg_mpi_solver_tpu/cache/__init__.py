"""Warm-path subsystem: persistent compile + partition caches.

Set-up, not iteration, dominated the earliest chip solves: about a
minute of partitioning at 10.33M dofs and minutes-long XLA compiles for
the bucketed octree variant.  This package
makes the SECOND solve of a given model/mesh shape cost near-zero setup —
the warm-start discipline production inference stacks apply to compiled
programs and KV caches:

* ``keys``            — content-addressed cache keys: model fingerprint +
                        (n_parts, backend, dtype, padding/partition knobs),
                        versioned by ``CACHE_SCHEMA`` and the package
                        version so a code bump invalidates cleanly.
* ``partition_cache`` — on-disk store for ``PartitionedModel`` /
                        ``HybridPartition`` / ``StructuredPartition``
                        (atomic zlib-pickled writes via ``utils/io.py``,
                        LRU eviction, stats).
* ``aot``             — placement of jax's persistent compilation cache
                        (``use_compilation_cache``) plus ahead-of-time
                        ``jax.export`` serialization of the jitted PCG
                        step, so a warm re-run of the same shape class
                        skips tracing AND compile.

Import contract: this ``__init__`` and ``keys`` / ``partition_cache`` are
jax-free at module load (``aot`` imports jax lazily inside functions) —
``bench.py`` and the CLI consult cache keys/stats before the accelerator
environment is configured.
"""

from pcg_mpi_solver_tpu.cache.keys import (
    CACHE_SCHEMA, array_hash, model_fingerprint, partition_cache_key,
    step_cache_key)
from pcg_mpi_solver_tpu.cache.partition_cache import (
    cache_stats, cached_partition, format_stats, load_partition,
    store_partition)

__all__ = [
    "CACHE_SCHEMA",
    "array_hash",
    "model_fingerprint",
    "partition_cache_key",
    "step_cache_key",
    "cache_stats",
    "cached_partition",
    "format_stats",
    "load_partition",
    "store_partition",
]
