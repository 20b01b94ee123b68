"""pcg_mpi_solver_tpu — a TPU-native massively-parallel matrix-free PCG framework.

A ground-up JAX/XLA/Pallas re-design of the capabilities of the reference
``ankitskr/PCG-MPI-solver`` (matrix-free preconditioned conjugate-gradient
solver for linear elastostatics on octree-pattern hexahedral meshes,
reference: /root/reference/src/solver/pcg_solver.py).

Design (TPU-first, not a port):

- The per-iteration hot kernel K.p is never assembled: elements are grouped by
  geometric pattern type; each group is one dense (d x d) @ (d x N) matmul on
  the MXU plus a single sorted ``segment_sum`` scatter-add
  (reference computes this per-rank with np.dot + np.bincount,
  pcg_solver.py:279,300).
- Domain decomposition maps to a ``jax.sharding.Mesh`` axis: one mesh
  partition = one device shard, all partitions padded to a common shape so the
  whole solve is ONE jitted SPMD program under ``shard_map``.
- The reference's Isend/Recv halo exchange (pcg_solver.py:317-334) becomes an
  "interface assembly": partial sums on shared dofs are scattered into a small
  global interface vector, combined with one ``lax.psum`` over the mesh axis,
  and gathered back.  Deterministic and ICI-friendly.
- Global reductions (allreduce, pcg_solver.py:622-628) are ``lax.psum``; the
  fused 3-norm reduction (pcg_solver.py:504-507) is kept as a single fused
  psum of a length-3 vector.
- The MATLAB-compatible PCG loop (flags/stagnation/best-iterate semantics,
  pcg_solver.py:356-598) runs entirely inside ``lax.while_loop`` — iterations
  never bounce back to the host.
"""

__version__ = "0.1.0"

from pcg_mpi_solver_tpu.config import SolverConfig, TimeHistoryConfig, RunConfig

__all__ = [
    "SolverConfig",
    "TimeHistoryConfig",
    "RunConfig",
    "__version__",
]
