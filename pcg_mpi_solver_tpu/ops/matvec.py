"""Device-side operators: matrix-free K.p, Jacobi diagonal, weighted dots.

THE hot kernel of the framework (reference: calcMatVecProd,
pcg_solver.py:242-336).  TPU-native formulation:

- per pattern-type group: gather -> sign-flip -> one dense
  ``Ke @ (ck * u)`` einsum on the MXU -> sign-flip back
  (reference does np.dot per rank, pcg_solver.py:277-280);
- scatter-add: all groups' element vectors concatenated, permuted into
  sorted-by-dof order (permutation precomputed on host), one
  ``segment_sum(indices_are_sorted=True)`` (reference: np.bincount 'outbin'
  mode, pcg_solver.py:294-300);
- cross-part assembly of shared ("interface") dofs: scatter partial sums into
  a small global interface vector, ONE ``lax.psum`` over the mesh axis, gather
  back (replaces the reference's tagged Isend/Recv halo exchange,
  pcg_solver.py:317-334 — deterministic, rides ICI);
- weighted dots with fp64 accumulation and the fused 3-norm reduction
  (reference: pcg_solver.py:462-507).

All functions run inside ``shard_map`` over a 1-D device mesh; arrays carry a
leading local-parts axis so multiple mesh partitions can be stacked per
device.  With ``axis_name=None`` the same code runs unsharded (single-device).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from pcg_mpi_solver_tpu.config import PCG_VARIANTS
from pcg_mpi_solver_tpu.parallel.partition import PartitionedModel

# ---------------------------------------------------------------------------
# Declared per-iteration collective contract of the PCG loop formulations
# (SolverConfig.pcg_variant).  ONE source of truth consumed by BOTH the
# telemetry gauges (Ops.comm_estimate below) and the static proof
# (analysis/ collective-budget rule + tools/check_collectives.py), so the
# advertised counts and the jaxpr-level verification can never diverge.
#
# * classic — MATLAB-compatible loop: three serialized scalar/fused psums
#   per iteration (rho+inf-prec, p.q, the fused 3-norm).
# * fused   — Chronopoulos–Gear recurrence: ONE fused psum carries all six
#   reduced scalars plus the inf-prec flag.
# * pipelined — Ghysels–Vanroose depth-1 pipelining: still ONE fused
#   psum, but its operands are all previous-iteration recurrence state
#   (r/u/w/p/x carry leaves), so the psum is data-INDEPENDENT of the
#   body's stencil matvec in both directions and the scheduler may
#   overlap them — the analysis/ psum-overlap rule proves that
#   independence statically, on top of this count.
#
# Changing a loop body (adding a pcg_variant) REQUIRES a row here: an
# unknown variant is a KeyError in both the gauges and the budget — the
# lint fails loudly instead of silently re-serializing.  The key set is
# pinned to the canonical config.PCG_VARIANTS name table (the single
# source the CLI/config/cache layers validate against) by the assert
# below: a variant added to one surface but not the other cannot import.
PCG_SCALAR_PSUMS = {"classic": 3, "fused": 1, "pipelined": 1}

if tuple(PCG_SCALAR_PSUMS) != PCG_VARIANTS:
    # an explicit raise, not `assert` — the guard must survive -O
    raise ImportError(
        "ops/matvec.PCG_SCALAR_PSUMS keys must match config.PCG_VARIANTS "
        "(the single-source variant name set): "
        f"{tuple(PCG_SCALAR_PSUMS)} != {PCG_VARIANTS}")

# The deferred mode-1 true-residual check lives INSIDE the traced while
# body (both branches of the conditional are part of the body jaxpr), and
# its recomputed residual norm costs one more psum on the trace — a
# healthy mode-0 trip never executes it, so it is budgeted separately
# from the per-iteration gauges.
PCG_DEFERRED_CHECK_PSUMS = 1

# Full-length vector UPDATES per committed iteration of each loop
# formulation (solver/pcg.py bodies) — the memory-bound axpy side of the
# per-iteration cost, consumed by the analytic cost model (obs/perf.py)
# next to the collective table above.  Counted from the loop bodies:
#
# * classic   — p = z + beta*p, x += alpha*p, r -= alpha*q        -> 3
# * fused     — p/q recurrences + x/r updates                     -> 4
# * pipelined — GV p/s/q/z recurrences + x/r/u/w updates          -> 8
#
# Same key-set pin as PCG_SCALAR_PSUMS: a new variant cannot land in one
# table without the other.
PCG_VECTOR_AXPYS = {"classic": 3, "fused": 4, "pipelined": 8}

if tuple(PCG_VECTOR_AXPYS) != PCG_VARIANTS:
    raise ImportError(
        "ops/matvec.PCG_VECTOR_AXPYS keys must match config.PCG_VARIANTS "
        "(the single-source variant name set): "
        f"{tuple(PCG_VECTOR_AXPYS)} != {PCG_VARIANTS}")

# ---------------------------------------------------------------------------
# Declared per-APPLY collective contract of the preconditioners
# (SolverConfig.precond), the same one-table discipline as
# PCG_SCALAR_PSUMS above: consumed by the telemetry gauges
# (Ops.comm_estimate), the static proof (analysis/ collective-budget
# rule via Ops.body_collective_budget), and docs.
#
# * jacobi / block3 — elementwise / small-matmul applies: zero
#   collectives of their own.
# * mg — one geometric V-cycle (ops/mg.py): 2*degree assembled
#   fine-level matvecs (degree-d Chebyshev pre-smoothing from zero =
#   d-1, the defect = 1, post-smoothing = d), each carrying exactly the
#   matvec's own interface collective, plus MG_RESTRICT_PSUMS to
#   assemble the restricted defect into the replicated coarse
#   hierarchy.  The smoother itself contributes ZERO collectives (fixed
#   Chebyshev polynomial, eigenvalue bounds precomputed at setup; the
#   whole coarse hierarchy is replicated) — every collective in the
#   cycle is matvec assembly or THE restriction.
#
# An unknown precond is a KeyError in both the gauges and the budget —
# the lint fails loudly instead of silently under-declaring.
MG_RESTRICT_PSUMS = 1
PRECOND_CYCLE_MATVECS = {"jacobi": 0, "block3": 0}


def precond_cycle_cost(precond: str, mg_degree: int = 2):
    """(extra assembled matvecs, extra standalone psums) per
    preconditioner APPLY.  Unknown precond = loud KeyError."""
    if precond == "mg":
        return 2 * int(mg_degree), MG_RESTRICT_PSUMS
    return PRECOND_CYCLE_MATVECS[precond], 0


def device_data(pm: PartitionedModel, dtype=jnp.float64,
                flat: Optional[bool] = None, blocks: bool = True) -> dict:
    """Pack a PartitionedModel into the pytree the ops consume, as host
    (numpy) arrays: ``parallel.distributed.put_tree`` then places each
    part's rows on the device that owns them, and nothing is staged on
    one device first.

    All leaves have a leading parts axis P (shard it over the mesh), except
    the small per-type constant matrices (Ke etc.), which are replicated.
    ``flat`` controls whether the flat-scatter arrays (dof/scat_perm/
    scat_ids) are included; by default they are uploaded only when the
    node-ELL fast path is unavailable (they are dead weight otherwise).
    ``blocks=False`` skips the per-type block arrays (for consumers that
    bring their own operator structure, e.g. the bucketed refresh amul,
    but still need the assembly/weight/load leaves).
    """
    if flat is None:
        flat = pm.ell is None and blocks

    def _blk(tb):
        b = {
            "Ke": np.asarray(tb.Ke, dtype),
            "diag_Ke": np.asarray(tb.diag_Ke, dtype),
            "Se": np.asarray(tb.Se, dtype) if tb.Se is not None else None,
            "sign": np.asarray(tb.sign),
            "node": np.asarray(tb.node, np.int32),
            "ck": np.asarray(tb.ck, dtype),
            "ce": np.asarray(tb.ce, dtype),
        }
        if flat:
            b["dof"] = np.asarray(tb.dof, np.int32)
        if pm.ell is not None:
            # node-component layouts for the node-ELL fast path: the element
            # matmul runs directly on gathered (node, elem, comp) rows, so
            # no runtime relayout of the (..., 3)-minor arrays is needed.
            nn = tb.d // 3
            b["Ke4"] = np.asarray(tb.Ke.reshape(nn, 3, nn, 3), dtype)
            b["diag_Ke4"] = np.asarray(tb.diag_Ke.reshape(nn, 3), dtype)
            b["sign_nc"] = np.ascontiguousarray(
                tb.sign.reshape(tb.sign.shape[0], nn, 3, -1)
                .transpose(0, 1, 3, 2))
        return b

    d = {
        "blocks": [_blk(tb) for tb in pm.type_blocks] if blocks else [],
        # the ELL scatter map is only consumed by the blocks path
        # (_scatter_rows); without blocks it would be ~1e8 int32 of dead
        # HBM at flagship scale
        "ell": (np.asarray(pm.ell, np.int32)
                if pm.ell is not None and blocks else None),
        "iface_local": np.asarray(pm.iface_local, np.int32),
        "iface_slot": np.asarray(pm.iface_slot, np.int32),
        "niface_local": np.asarray(pm.niface_local, np.int32),
        "niface_slot": np.asarray(pm.niface_slot, np.int32),
        "weight": np.asarray(pm.weight, dtype),
        "node_weight": np.asarray(pm.node_weight, dtype),
        "eff": np.asarray(pm.eff, dtype),
        "F": np.asarray(pm.F, dtype),
        "Ud": np.asarray(pm.Ud, dtype),
    }
    if flat:
        d["scat_perm"] = np.asarray(pm.scat_perm, np.int32)
        d["scat_ids"] = np.asarray(pm.scat_ids, np.int32)
    if pm.spr_a is not None:
        # cohesive interface springs (PartitionedModel spr_*)
        d["spr_a"] = np.asarray(pm.spr_a, np.int32)
        d["spr_b"] = np.asarray(pm.spr_b, np.int32)
        d["spr_k"] = np.asarray(pm.spr_k, dtype)
    return d


def with_f32_shadow(data: dict) -> dict:
    """The mixed-precision tree ``{"f64": data, "f32": shadow}``: the
    shadow casts the float leaves to float32 on the host and holds every
    other leaf (index and bool arrays) as the same object, which
    ``put_tree`` then uploads once for both trees."""
    return {"f64": data,
            "f32": jax.tree.map(
                lambda x: (x.astype(np.float32)
                           if np.issubdtype(x.dtype, np.floating) else x),
                data)}


#: The named stages every backend's matvec is traced in (``jax.named_scope``
#: labels; the analysis/ ``scope-labels`` rule proves each appears):
#: dof vector to element or grid layout, the per-type ``Ke`` product,
#: element or grid values back to dofs, and the cross-part assembly.
MATVEC_STAGES = ("mv/gather", "mv/contract", "mv/scatter", "mv/halo")


@dataclasses.dataclass(frozen=True)
class Ops:
    """Static-shape metadata + the operator methods.

    Construct once per partitioned model; methods are pure and traceable.
    ``axis_name`` is the mesh axis inside shard_map (None = unsharded).
    """

    n_loc: int
    n_iface: int
    n_node_loc: int = 0
    n_node_iface: int = 0
    dot_dtype: jnp.dtype = jnp.float64
    axis_name: Optional[str] = None
    # Node-ELL fast path: gather/scatter move (node, 3) ROWS instead of
    # scalar dofs — TPU row-gathers are ~an order of magnitude faster than
    # scalar gathers, and scatter-add becomes row-gather + row-sum over the
    # precomputed ELL map (PartitionedModel.ell).
    use_node_ell: bool = False
    # MXU precision for the element matmuls.  TPU 'default' runs f32 inputs
    # through low-precision bf16 passes, which caps the attainable PCG
    # residual far above tol; HIGHEST is fp32-true (6-pass bf16) and still
    # rides the MXU.
    precision: jax.lax.Precision = jax.lax.Precision.HIGHEST
    # Chebyshev smoothing degree of the MG V-cycle preconditioner
    # (SolverConfig.mg_smooth_degree, pinned here at solver construction
    # because it shapes the traced cycle: 2*degree fine matvecs per
    # apply — precond_cycle_cost above).  Unused unless the prec operand
    # is the mg dict (ops/mg.py).
    mg_degree: int = 2
    # Replicated first-coarse vector length (ops/mg.coarse_dofs — the
    # restriction psum's payload), pinned alongside mg_degree so
    # comm_estimate can report the V-cycle's full psum traffic.
    mg_coarse_dofs: int = 0

    @classmethod
    def from_model(cls, pm: PartitionedModel, dot_dtype=jnp.float64, axis_name=None,
                   precision=jax.lax.Precision.HIGHEST):
        return cls(n_loc=pm.n_loc, n_iface=pm.n_iface,
                   n_node_loc=pm.n_node_loc, n_node_iface=pm.n_node_iface,
                   dot_dtype=dot_dtype, axis_name=axis_name, precision=precision,
                   use_node_ell=pm.ell is not None)

    # -- collectives ----------------------------------------------------
    def _psum(self, x):
        if self.axis_name is None:
            return x
        return jax.lax.psum(x, self.axis_name)

    # -- interface assembly --------------------------------------------
    def _assemble_shared(self, y, local, slot, n_glob):
        """Sum partial values of ids shared by several parts: scatter into a
        global shared-id vector, ONE psum, gather back.  y: (P, n) or, with
        a trailing RHS-block axis, (P, n, R) — the psum payload widens to
        (n_glob, R) but the collective COUNT stays one either way (the
        batched-solve contract, tools/check_collectives.py).

        Traced in three scopes, nested in the caller's (``mv/halo`` in a
        matvec): ``pack`` (take the part's shared entries, scatter-add
        them into the shared-id vector), ``psum`` and ``unpack`` (gather
        the sums back, set them into the part's vector)."""
        tail = y.shape[2:]                                  # () or (R,)
        with jax.named_scope("pack"):
            vals = jnp.take_along_axis(
                y, local.reshape(local.shape + (1,) * len(tail)), axis=1,
                mode="fill", fill_value=0)
            glob = jnp.zeros((n_glob,) + tail, y.dtype)
            glob = glob.at[slot.reshape(-1)].add(
                vals.reshape((-1,) + tail), mode="drop")
        with jax.named_scope("psum"):
            glob = self._psum(glob)
        with jax.named_scope("unpack"):
            new = glob.at[slot].get(mode="fill", fill_value=0)
            return jax.vmap(
                lambda yp, loc, nv: yp.at[loc].set(nv, mode="drop"))(
                y, local, new)

    def iface_assemble(self, data: dict, y: jnp.ndarray) -> jnp.ndarray:
        """Dof-space assembly: (P, n_loc) partial sums -> fully assembled."""
        if self.n_iface == 0:
            return y
        return self._assemble_shared(y, data["iface_local"],
                                     data["iface_slot"], self.n_iface)

    def niface_assemble(self, data: dict, y: jnp.ndarray) -> jnp.ndarray:
        """Node-space assembly for (P, k, n_node_loc) stacked channels
        (reference exchanges nodal sums+counts over neighbors,
        pcg_solver.py:689-723)."""
        if self.n_node_iface == 0:
            return y
        f = lambda yk: self._assemble_shared(
            yk, data["niface_local"], data["niface_slot"], self.n_node_iface)
        return jax.vmap(f, in_axes=1, out_axes=1)(y)

    # -- gather/scatter primitives (node-ELL fast path + flat fallback) --
    #
    # The parts axis is folded into the gather row index (ids + p*stride into
    # a (P*rows, 3) view) instead of vmap-ing per part: batched (vmap) TPU
    # gathers measured 4-5x slower than a single flat row gather.  A zero
    # pad row per part keeps all padded indices in bounds.

    def _gather_u3(self, x: jnp.ndarray, blk: dict) -> jnp.ndarray:
        """x (P, n_loc[, R]) -> gathered node rows (P, nn, N, 3[, R]).
        The RHS-block axis rides the gathered row as extra minor dims —
        same single flat row gather, wider rows."""
        node = blk["node"]                                   # (P, nn, N)
        Pn, nn, N = node.shape
        nr = self.n_node_loc + 1
        tail = x.shape[2:]                                   # () or (R,)
        x3 = x.reshape((Pn, self.n_node_loc, 3) + tail)
        x3p = jnp.concatenate(
            [x3, jnp.zeros((Pn, 1, 3) + tail, x3.dtype)],
            axis=1).reshape((Pn * nr, 3) + tail)
        offs = (jnp.arange(Pn, dtype=jnp.int32) * nr)[:, None, None]
        u3 = jnp.take(x3p, (node + offs).reshape(-1), axis=0, mode="clip")
        return u3.reshape((Pn, nn, N, 3) + tail)

    def _gather_u(self, data: dict, x: jnp.ndarray, blk: dict) -> jnp.ndarray:
        """x (P, n_loc[, R]) -> element dof values (P, d, N[, R])."""
        if self.use_node_ell:
            u3 = self._gather_u3(x, blk)
            Pn, nn, N = u3.shape[:3]
            # row (a, n, c) -> dof row 3a+c of column n
            if u3.ndim == 5:
                return u3.transpose(0, 1, 3, 2, 4).reshape(
                    Pn, 3 * nn, N, u3.shape[4])
            return u3.transpose(0, 1, 3, 2).reshape(Pn, 3 * nn, N)
        if x.ndim == 3:
            return jnp.take_along_axis(
                x[:, None, :, :], blk["dof"][:, :, :, None], axis=2,
                mode="fill", fill_value=0)
        return jnp.take_along_axis(x[:, None, :], blk["dof"], axis=2,
                                   mode="fill", fill_value=0)

    def _scatter_rows(self, data: dict, rows) -> jnp.ndarray:
        """Per-block (P, nn*N, 3[, R]) value rows -> local dof sums
        (P, n_loc[, R]) via the ELL map: one row gather + row-sum, no
        scatter-add."""
        flat3 = jnp.concatenate(rows, axis=1)                # (P, NCn, 3[, R])
        Pn, ncn = flat3.shape[:2]
        tail = flat3.shape[3:]
        flat3p = jnp.concatenate(
            [flat3, jnp.zeros((Pn, 1, 3) + tail, flat3.dtype)],
            axis=1).reshape((Pn * (ncn + 1), 3) + tail)
        ell = data["ell"]                                    # (P, n_node_loc, K)
        offs = (jnp.arange(Pn, dtype=jnp.int32) * (ncn + 1))[:, None, None]
        g = jnp.take(flat3p, (ell + offs).reshape(-1), axis=0, mode="clip")
        y3 = g.reshape((Pn, self.n_node_loc, -1, 3) + tail).sum(axis=2)
        return y3.reshape((Pn, self.n_loc) + tail)

    def _scatter_blocks(self, data: dict, per_block_v) -> jnp.ndarray:
        """Per-block element values [(P, d, N[, R])] -> local dof sums
        (P, n_loc[, R])."""
        if self.use_node_ell:
            rows = []
            for v in per_block_v:
                Pn, d, N = v.shape[:3]
                nn = d // 3
                # dof row 3a+c -> value row a*N+n, component c
                if v.ndim == 4:
                    rows.append(
                        v.reshape(Pn, nn, 3, N, v.shape[3])
                        .transpose(0, 1, 3, 2, 4)
                        .reshape(Pn, nn * N, 3, v.shape[3]))
                else:
                    rows.append(v.reshape(Pn, nn, 3, N)
                                .transpose(0, 1, 3, 2)
                                .reshape(Pn, nn * N, 3))
            return self._scatter_rows(data, rows)
        flat = jnp.concatenate(
            [v.reshape((v.shape[0], -1) + v.shape[3:]) for v in per_block_v],
            axis=1)
        return self._scatter(data, flat)

    # -- the matvec -----------------------------------------------------
    def _element_rows(self, data: dict, x: jnp.ndarray) -> list:
        """Node-ELL layout: per type block, the element values of K.x as
        (P, nn*N, 3[, R]) node rows (row a*N+n, the slot order of
        PartitionedModel.ell), traced in ``mv/gather`` and
        ``mv/contract``."""
        blocked = x.ndim == 3
        rows = []
        for blk in data["blocks"]:
            with jax.named_scope("mv/gather"):
                sgn = (blk["sign_nc"][..., None] if blocked
                       else blk["sign_nc"])
                u3 = self._gather_u3(x, blk)         # (P, a, n, c[, r])
                u3 = jnp.where(sgn, -u3, u3)
            with jax.named_scope("mv/contract"):
                ck = blk["ck"][:, None, :, None]
                if blocked:
                    v = jnp.einsum("bdac,pancr->pbndr", blk["Ke4"],
                                   ck[..., None] * u3,
                                   precision=self.precision)
                else:
                    v = jnp.einsum("bdac,panc->pbnd", blk["Ke4"],
                                   ck * u3,
                                   precision=self.precision)
                v = jnp.where(sgn, -v, v)            # (P, b, n, d)
                Pn, nn, N = v.shape[:3]
                rows.append(v.reshape((Pn, nn * N, 3) + x.shape[2:]))
        return rows

    def matvec_local(self, data: dict, x: jnp.ndarray) -> jnp.ndarray:
        """Part-local K.x (no cross-part assembly).  x: (P, n_loc), or
        (P, n_loc, nrhs) for a RHS block — then every per-type matmul
        batches over the trailing axis ((d x d) @ (d x N x nrhs): higher
        MXU utilization at near-constant gather/scatter traffic, the
        ISSUE-6 batched-SpMV shape) and the result keeps the block axis."""
        # Traced in three named stages: ``mv/gather`` (dof vector to
        # element values), ``mv/contract`` (the per-type ``Ke`` product)
        # and ``mv/scatter`` (element values back to dofs, springs
        # included), so a profiler trace splits the matvec's time by stage.
        blocked = x.ndim == 3
        if self.use_node_ell:
            rows = self._element_rows(data, x)
        else:
            per_block_v = []
            for blk in data["blocks"]:
                with jax.named_scope("mv/gather"):
                    sgn = blk["sign"][..., None] if blocked else blk["sign"]
                    u = self._gather_u(data, x, blk)         # (P, d, N[, r])
                    u = jnp.where(sgn, -u, u)
                with jax.named_scope("mv/contract"):
                    ck = blk["ck"][:, None, :]
                    if blocked:
                        v = jnp.einsum("de,penr->pdnr", blk["Ke"],
                                       ck[..., None] * u,
                                       precision=self.precision)
                    else:
                        v = jnp.einsum("de,pen->pdn", blk["Ke"],
                                       ck * u,
                                       precision=self.precision)
                    v = jnp.where(sgn, -v, v)
                per_block_v.append(v)
        with jax.named_scope("mv/scatter"):
            y = (self._scatter_rows(data, rows) if self.use_node_ell
                 else self._scatter_blocks(data, per_block_v))
            return self._apply_springs(data, x, y)

    def _apply_springs(self, data: dict, x, y):
        """Cohesive interface springs: f_a += k*(x_a - x_b), f_b -= same
        (a live capability where the reference has only scaffolding,
        partition_mesh.py:603-650); padded entries have k = 0 and
        out-of-bounds ids, so they gather 0 and drop on scatter."""
        if "spr_a" not in data:
            return y
        if x.ndim == 3:
            ia, ib = data["spr_a"][:, :, None], data["spr_b"][:, :, None]
            xa = jnp.take_along_axis(x, ia, axis=1, mode="fill",
                                     fill_value=0)
            xb = jnp.take_along_axis(x, ib, axis=1, mode="fill",
                                     fill_value=0)
            f = data["spr_k"][..., None] * (xa - xb)
        else:
            xa = jnp.take_along_axis(x, data["spr_a"], axis=1,
                                     mode="fill", fill_value=0)
            xb = jnp.take_along_axis(x, data["spr_b"], axis=1,
                                     mode="fill", fill_value=0)
            f = data["spr_k"] * (xa - xb)
        return jax.vmap(
            lambda yp, ia, ib, fp: yp.at[ia].add(fp, mode="drop")
                                     .at[ib].add(-fp, mode="drop")
        )(y, data["spr_a"], data["spr_b"], f)

    def diag_local(self, data: dict) -> jnp.ndarray:
        """Part-local diag(K) via the same scatter path
        (reference 'Preconditioner' mode, pcg_solver.py:282-287)."""
        if self.use_node_ell:
            rows = []
            for blk in data["blocks"]:
                ck = blk["ck"]                               # (P, N)
                v = (blk["diag_Ke4"][None, :, None, :]
                     * ck[:, None, :, None])                 # (P, nn, N, 3)
                rows.append(v.reshape(ck.shape[0], -1, 3))
            y = self._scatter_rows(data, rows)
        else:
            per_block_v = [
                jnp.broadcast_to(
                    blk["diag_Ke"][None, :, None] * blk["ck"][:, None, :],
                    (blk["ck"].shape[0], blk["diag_Ke"].shape[0],
                     blk["ck"].shape[1]))
                for blk in data["blocks"]
            ]
            y = self._scatter_blocks(data, per_block_v)
        return self._apply_springs_diag(data, y)

    def _apply_springs_diag(self, data: dict, y):
        if "spr_a" not in data:
            return y
        return jax.vmap(
            lambda yp, ia, ib, kp: yp.at[ia].add(kp, mode="drop")
                                     .at[ib].add(kp, mode="drop")
        )(y, data["spr_a"], data["spr_b"], data["spr_k"])

    # -- node-block (3x3) diagonal for block-Jacobi ---------------------
    def _node_block_local(self, data: dict) -> jnp.ndarray:
        """Part-local per-node 3x3 diagonal blocks of K, flattened to
        (P, n_node_loc, 9) row-major.  Same assembly path as diag_local but
        keeping the full within-node coupling K[3a+i, 3a+j]; mirrored
        patterns scale entry (i, j) by sign_i*sign_j (the diag's sign^2 == 1
        generalized off the diagonal)."""
        if not self.use_node_ell:
            raise ValueError(
                "block-Jacobi needs the node-contiguous dof layout "
                "(PartitionedModel.ell); this model/partition lacks it — "
                "use precond='jacobi'")
        Pl = data["weight"].shape[0]
        dt = data["weight"].dtype
        out = jnp.zeros((Pl, self.n_node_loc, 9), dt)
        for blk in data["blocks"]:
            node = blk["node"]                            # (P, nn, N)
            Pn, nn, N = node.shape
            Ke4 = blk["Ke4"]                              # (nn, 3, nn, 3)
            D = jnp.stack([Ke4[a, :, a, :] for a in range(nn)])  # (nn, 3, 3)
            sv = jnp.where(blk["sign"], -1.0, 1.0).astype(dt) \
                .reshape(Pn, nn, 3, N)
            contrib = jnp.einsum("aij,pn,pain,pajn->panij",
                                 D, blk["ck"], sv, sv,
                                 precision=self.precision)
            out = jax.vmap(
                lambda o, idx, r: o.at[idx].add(r, mode="drop")
            )(out, node.reshape(Pn, -1),
              contrib.reshape(Pn, nn * N, 9))
        return self._springs_into_blocks(data, out)

    def _springs_into_blocks(self, data: dict, out):
        """Cohesive-spring diagonal contributions into the (i, i) entries of
        the endpoint nodes' blocks (off-node coupling is dropped — the
        preconditioner is approximate there, like scalar Jacobi)."""
        if "spr_a" not in data:
            return out
        Pl = out.shape[0]
        flat = out.reshape(Pl, -1)

        def add(fp, dof, kp):
            idx = (dof // 3) * 9 + (dof % 3) * 4
            return fp.at[idx].add(kp, mode="drop")

        flat = jax.vmap(add)(flat, data["spr_a"], data["spr_k"])
        flat = jax.vmap(add)(flat, data["spr_b"], data["spr_k"])
        return flat.reshape(out.shape)

    def node_block_diag(self, data: dict) -> jnp.ndarray:
        """Fully assembled per-node 3x3 diagonal blocks (P, n_node_loc,
        3, 3): local blocks summed across parts sharing the node (same
        psum assembly as the scalar diag)."""
        y = self._node_block_local(data)                  # (P, n, 9)
        y = self.niface_assemble(data, y.transpose(0, 2, 1)).transpose(0, 2, 1)
        return y.reshape(y.shape[0], self.n_node_loc, 3, 3)

    def _as_node3(self, v: jnp.ndarray) -> jnp.ndarray:
        """(P, n_loc[, R]) dof vector -> (P, n_node_loc, 3[, R]) node rows
        (the node-contiguous layout; StructuredOps overrides for its
        component-major grid layout)."""
        return v.reshape((v.shape[0], self.n_node_loc, 3) + v.shape[2:])

    def _from_node3(self, z3: jnp.ndarray) -> jnp.ndarray:
        """Inverse of :meth:`_as_node3`: (P, n_node_loc, 3[, R]) ->
        (P, n_loc[, R])."""
        return z3.reshape((z3.shape[0], self.n_loc) + z3.shape[3:])

    def block_precond(self, data: dict) -> jnp.ndarray:
        """Inverted eff-masked node blocks, ready for ``apply_prec``."""
        from pcg_mpi_solver_tpu.ops.precond import invert_node_blocks

        return invert_node_blocks(self.node_block_diag(data),
                                  self._as_node3(data["eff"]))

    def apply_prec(self, m, r: jnp.ndarray, data: dict = None) -> jnp.ndarray:
        """z = M^-1 r: elementwise for the scalar Jacobi inverse (ndim 2),
        batched 3x3 block multiply for the block-Jacobi inverse (ndim 4),
        or one geometric multigrid V-cycle when ``m`` is the mg prec
        dict (ops/mg.py — then ``data`` must be the device data tree the
        hierarchy rides, which every PCG body has in scope); backend dof
        layouts differ only through _as_node3/_from_node3.
        ``r`` may carry a trailing RHS-block axis (P, n_loc, nrhs)."""
        if isinstance(m, dict):
            from pcg_mpi_solver_tpu.ops.mg import mg_apply

            return mg_apply(self, data, m, r)
        blocked = r.ndim == 3
        if m.ndim == 2:
            return m[..., None] * r if blocked else m * r
        if blocked:
            z3 = jnp.einsum("pnij,pnjr->pnir", m, self._as_node3(r),
                            precision=self.precision)
        else:
            z3 = jnp.einsum("pnij,pnj->pni", m, self._as_node3(r),
                            precision=self.precision)
        return self._from_node3(z3)

    def _scatter(self, data: dict, flat: jnp.ndarray) -> jnp.ndarray:
        """(P, NC[, R]) element-dof values -> (P, n_loc[, R]) via sorted
        segment_sum (the RHS block rides as a trailing segment dim)."""
        perm = (data["scat_perm"][:, :, None] if flat.ndim == 3
                else data["scat_perm"])
        svals = jnp.take_along_axis(flat, perm, axis=1)
        seg = jax.vmap(
            partial(jax.ops.segment_sum, num_segments=self.n_loc + 1,
                    indices_are_sorted=True)
        )(svals, data["scat_ids"])
        return seg[:, : self.n_loc]

    def matvec(self, data: dict, x: jnp.ndarray) -> jnp.ndarray:
        """Full assembled K.x across all parts (reference calcMPFint).
        ``x`` may carry a trailing RHS-block axis (P, n_loc, nrhs); the
        result keeps it, and the interface-assembly psum count stays ONE
        regardless of the block width.  The assembly is the ``mv/halo``
        stage (the local product's stages: :meth:`matvec_local`)."""
        y = self.matvec_local(data, x)
        with jax.named_scope("mv/halo"):
            return self.iface_assemble(data, y)

    def comm_estimate(self, storage_dtype=None,
                      variant: str = "classic",
                      precond: str = "jacobi") -> dict:
        """Static per-PCG-iteration collective estimate from the ops
        shapes, for the telemetry gauges (obs/metrics.py).  ``variant``
        is the PCG loop formulation (SolverConfig.pcg_variant): classic
        runs 3 serialized scalar/fused psums per iteration (rho+inf, pq,
        fused 3-norm — 6 reduced scalars total); the fused
        Chronopoulos–Gear variant folds all 6 scalars into ONE psum.
        Either way the interface-assembly psum inside the matvec adds
        one collective whose payload is the shared-dof vector.
        ``bytes_per_iter_est`` is the per-device psum payload, not link
        traffic (the actual wire cost depends on the all-reduce
        algorithm and topology).

        The per-iteration scalar-psum count comes from
        ``PCG_SCALAR_PSUMS`` (declared above) — the SAME table the
        collective-budget lint rule (analysis/) proves against the
        traced loop-body jaxpr, so these gauges can never advertise a
        count the static proof does not hold."""
        itemsize = jnp.dtype(storage_dtype if storage_dtype is not None
                             else self.dot_dtype).itemsize
        dot_bytes = jnp.dtype(self.dot_dtype).itemsize
        n_iface = int(self.n_iface)
        scalar_psums = PCG_SCALAR_PSUMS[variant]
        # preconditioner-apply collectives (precond_cycle_cost — the mg
        # V-cycle's fine matvec assemblies + restriction psum; jacobi/
        # block3 add zero): same table the collective-budget rule
        # proves.  The restriction psum's payload is the replicated
        # first-coarse vector (mg_coarse_dofs, pinned at construction)
        # — the largest single collective payload of the cycle, so the
        # bytes estimate must carry it.
        mv_extra, ps_extra = precond_cycle_cost(precond, self.mg_degree)
        return {
            "pcg_variant": variant,
            "precond": precond,
            "psums_per_iter": (scalar_psums
                               + ((1 + mv_extra) if n_iface else 0)
                               + ps_extra),
            "iface_dofs": n_iface,
            "reduce_scalars_per_iter": 6,
            "bytes_per_iter_est": (n_iface * itemsize * (1 + mv_extra)
                                   + ps_extra * int(self.mg_coarse_dofs)
                                   * itemsize
                                   + 6 * dot_bytes),
        }

    def body_collective_budget(self, variant: str = "classic",
                               precond: str = "jacobi") -> dict:
        """Per-primitive collective budget of the TRACED PCG while-loop
        body, the contract the analysis/ collective-budget rule proves
        against every canonical program's jaxpr (and the single source
        ``tools/check_collectives.py`` documents).  Differs from the
        healthy-iteration gauge above because the traced body carries
        BOTH conditional branches: the deferred mode-1 true-residual
        check contributes ``PCG_DEFERRED_CHECK_PSUMS`` extra norm
        psum(s) that a healthy (mode-0) trip never executes.  Keyed per
        primitive so a re-serialized reduction OR a new collective kind
        sneaking into the hot body both fail the lint.

        ``precond`` extends the budget with the preconditioner apply's
        declared collectives (``precond_cycle_cost``): the mg V-cycle
        adds ``2*mg_degree`` assembled fine matvecs (each one interface
        psum when the partition has shared dofs) plus the restriction
        psum; the smoother itself contributes zero.  Unknown precond =
        loud KeyError."""
        mv_extra, ps_extra = precond_cycle_cost(precond, self.mg_degree)
        psums = PCG_SCALAR_PSUMS[variant] + PCG_DEFERRED_CHECK_PSUMS
        if int(self.n_iface):
            psums += 1 + mv_extra
        if self.axis_name is not None:
            psums += ps_extra
        return {"psum": psums}

    def diag(self, data: dict) -> jnp.ndarray:
        return self.iface_assemble(data, self.diag_local(data))

    # -- element strain + nodal averaging (export path) -----------------
    def elem_strain(self, data: dict, x: jnp.ndarray):
        """Per-block center-point strain eps = Se @ (ce * S.u_e), in each
        pattern's local frame (reference updateElemStrain,
        pcg_solver.py:601-618).  Returns list of (P, 6, N)."""
        out = []
        for blk in data["blocks"]:
            u = self._gather_u(data, x, blk)
            u = jnp.where(blk["sign"], -u, u)
            eps = jnp.einsum("sd,pdn->psn", blk["Se"],
                             blk["ce"][:, None, :] * u, precision=self.precision)
            out.append(eps)
        return out

    def elem_scale(self, data: dict):
        """Per-block elastic modulus E = ck*ce (since ck=E*h, ce=1/h)."""
        return [blk["ck"] * blk["ce"] for blk in data["blocks"]]

    def nodal_average(self, data: dict, vals_list) -> jnp.ndarray:
        """Element values -> averaged nodal field.

        vals_list: per block (P, k, N) element-constant values.  Scatter
        sums + counts to element nodes, assemble shared nodes across parts,
        divide (reference getNodalScalarVar/getNodalPS,
        pcg_solver.py:655-814, incl. the +1e-15 guard :724)."""
        k = vals_list[0].shape[1]
        Pl = vals_list[0].shape[0]
        dt = vals_list[0].dtype
        sums = jnp.zeros((Pl, k, self.n_node_loc), dt)
        counts = jnp.zeros((Pl, 1, self.n_node_loc), dt)

        def scat(s, ids, c):
            return s.at[:, ids].add(c, mode="drop")

        for blk, vals in zip(data["blocks"], vals_list):
            node = blk["node"]                        # (P, nn, N)
            nn = node.shape[1]
            ids = node.reshape(Pl, -1)
            contrib = jnp.broadcast_to(vals[:, :, None, :],
                                       (Pl, k, nn, vals.shape[2])
                                       ).reshape(Pl, k, -1)
            # Every real element counts once per node (reference
            # pcg_solver.py:685-686); padded slots drop via their
            # out-of-bounds node ids, so no extra masking — identical
            # semantics on both backends.
            ones = jnp.ones((Pl, 1, nn * vals.shape[2]), dt)
            sums = jax.vmap(scat)(sums, ids, contrib)
            counts = jax.vmap(scat)(counts, ids, ones)

        both = jnp.concatenate([sums, counts], axis=1)
        both = self.niface_assemble(data, both)
        return both[:, :k] / (both[:, k:] + 1e-15)

    # -- reductions -----------------------------------------------------
    def _local_dot(self, w, a, b):
        # Cast operands BEFORE multiplying: products of two f32 values are
        # exact in f64, so f32-storage runs get true f64-accumulated dots.
        dd = self.dot_dtype
        return jnp.sum(a.astype(dd) * b.astype(dd) * w.astype(dd))

    def wdot(self, w: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        """Global weighted dot <a, b>_w: duplicated interface dofs counted
        once via the 0/1 owner weights (reference pcg_solver.py:381,462)."""
        return self._psum(self._local_dot(w, a, b))

    def wdots(self, w: jnp.ndarray, pairs, extra=()) -> jnp.ndarray:
        """Fused multi-dot: ONE psum for several dots, optionally carrying
        extra pre-reduced local scalars in the same collective
        (reference's fused 3-norm allreduce, pcg_solver.py:504-507)."""
        loc = jnp.stack([self._local_dot(w, a, b) for a, b in pairs]
                        + [jnp.asarray(e, self.dot_dtype) for e in extra])
        return self._psum(loc)

    # -- per-RHS reductions (the batched-solve contract) ----------------
    def _local_dot_many(self, w, a, b):
        """Per-column local weighted dots of an RHS block: a, b
        (P, n_loc, R) -> (R,).  vmapped over the trailing axis so each
        column's reduction is the SAME reduce the single-RHS
        :meth:`_local_dot` runs (bit-identical per column on CPU — the
        classic-parity contract of tests/test_pcg_many.py)."""
        return jax.vmap(lambda ac, bc: self._local_dot(w, ac, bc),
                        in_axes=(-1, -1))(a, b)

    def wdot_many(self, w: jnp.ndarray, a: jnp.ndarray,
                  b: jnp.ndarray) -> jnp.ndarray:
        """Per-RHS global weighted dots <a_j, b_j>_w: (P, n_loc, R) ->
        (R,) in ONE psum — the collective count is independent of the
        block width; only the payload widens."""
        return self._psum(self._local_dot_many(w, a, b))

    def wdots_many(self, w: jnp.ndarray, pairs, extra=()) -> jnp.ndarray:
        """Fused per-RHS multi-dot: pairs of (P, n_loc, R) blocks (plus
        optional pre-reduced (R,) local rows) -> (k + len(extra), R) in
        ONE psum.  The batched twin of :meth:`wdots`: every per-RHS
        scalar reduction of a PCG iteration folds into a single
        collective whose payload scales with nrhs but whose COUNT does
        not (tools/check_collectives.py proves this statically)."""
        loc = jnp.stack([self._local_dot_many(w, a, b) for a, b in pairs]
                        + [jnp.asarray(e, self.dot_dtype) for e in extra])
        return self._psum(loc)


# ---------------------------------------------------------------------------
# Bucketed matvec: a compile-cheap operator formulation for out-of-loop use.
#
# The per-type loop above emits one gather/einsum/scatter structure PER
# pattern type; at the reference's deep-graded octrees that is 200+ types
# (/root/reference/src/solver/partition_mesh.py:1074 allows <=144 per rank,
# multi-part models exceed it globally), and measured chipless compile cost
# tracks the emitted structure COUNT, not FLOPs (227 type blocks -> 1343 s
# f64).  Here types are STACKED into
# a few buckets by element-count SIZE CLASS only (power-of-16 boundaries;
# ~5 buckets at the flagship), with element arity (d, nn) zero-padded to
# each bucket's max: one batched einsum per bucket.  Element-count slots
# pad to the bucket max and arity padding can cost up to ~16x on the
# small transition types — irrelevant for the ~4 calls/solve refresh
# amul this exists for (the dominant brick type sits alone in the top
# size class and pays no padding).  The scatter is an unordered at[].add
# (bit-order differs from the type-loop path), so this formulation is for
# paths WITHOUT a bit-exact iteration contract (the mixed-mode f64
# refresh; never the direct/f64 parity path).

def build_bucketed_blocks(pm: PartitionedModel, dtype=jnp.float64):
    """Stack pm.type_blocks into padded same-shape buckets (host arrays,
    like :func:`device_data`).

    Returns a list of dicts {"Ke": (T, d, d), "node": (P, T, nn, Nmax),
    "sign": (P, T, d, Nmax), "ck": (P, T, Nmax)} — parts axis LEADING on
    the per-part arrays (the driver's _data_specs shards leaf axis 0).
    Padded slots carry ck = 0 and node = n_node_loc (the gather's zero
    row / the scatter's dropped out-of-bounds row)."""
    if pm.ell is None:
        raise ValueError("bucketed matvec requires the 3-dof node layout "
                         "(PartitionedModel.ell)")
    groups: dict = {}
    for tb in pm.type_blocks:
        if tb.d != 3 * tb.n_nodes:
            raise ValueError(f"type {tb.type_id}: d={tb.d} is not "
                             f"3*n_nodes={tb.n_nodes} — not node layout")
        N = tb.node.shape[2]
        size_cls = 0
        # coarse power-of-16 classes: N <= 16, 256, 4096, 65k, 1M, ...
        # Grouping is by size class ONLY — element arity (d, nn) is
        # zero-PADDED to the bucket max instead of splitting buckets:
        # measured at the flagship, (d, nn, cls) grouping still left
        # 36-40 buckets (the reference's hanging-node transition types
        # span many arities) while compile cost tracks bucket COUNT
        # (general 227 structs 1343 s / 40 buckets 680 s / stencil 999 s
        # chipless).  The dominant brick type sits alone in the top size
        # class, so the arity padding wastes FLOPs only on the small
        # transition types — irrelevant for an out-of-loop operator.
        while 16 ** (size_cls + 1) < N:
            size_cls += 1
        groups.setdefault(size_cls, []).append(tb)
    buckets = []
    for _cls, tbs in sorted(groups.items()):
        P = tbs[0].node.shape[0]
        nmax = max(tb.node.shape[2] for tb in tbs)
        nn = max(tb.n_nodes for tb in tbs)
        d = 3 * nn
        T = len(tbs)
        Ke = np.zeros((T, d, d))
        node = np.full((P, T, nn, nmax), pm.n_node_loc, dtype=np.int32)
        sign = np.zeros((P, T, d, nmax), dtype=bool)
        ck = np.zeros((P, T, nmax))
        for t, tb in enumerate(tbs):
            n = tb.node.shape[2]
            Ke[t, :tb.d, :tb.d] = tb.Ke
            node[:, t, :tb.n_nodes, :n] = tb.node
            sign[:, t, :tb.d, :n] = tb.sign
            ck[:, t, :n] = tb.ck
        buckets.append({"Ke": np.asarray(Ke, dtype), "node": node,
                        "sign": sign, "ck": np.asarray(ck, dtype)})
    return buckets


def bucketed_matvec(ops: Ops, data: dict, x: jnp.ndarray) -> jnp.ndarray:
    """Assembled K.x via the bucketed blocks (data["buckets"] +
    device_data(..., blocks=False) leaves).  Same contract as
    Ops.matvec; summation order differs (see module note above).  Same
    ``mv/*`` stages as Ops.matvec."""
    Pn = x.shape[0]
    nr = ops.n_node_loc + 1
    with jax.named_scope("mv/gather"):
        x3p = jnp.concatenate(
            [x.reshape(Pn, ops.n_node_loc, 3),
             jnp.zeros((Pn, 1, 3), x.dtype)], axis=1).reshape(Pn * nr, 3)
        offs = (jnp.arange(Pn, dtype=jnp.int32) * nr)[:, None, None, None]
    with jax.named_scope("mv/scatter"):
        y3 = jnp.zeros((Pn, ops.n_node_loc, 3), x.dtype)
    for bkt in data["buckets"]:
        node = bkt["node"]                              # (P, T, nn, Nmax)
        _, T, nn, N = node.shape
        with jax.named_scope("mv/gather"):
            u3 = jnp.take(x3p, (node + offs).reshape(-1), axis=0,
                          mode="clip").reshape(Pn, T, nn, N, 3)
            # dof-row order d = 3a + c, matching TypeBlock.sign's layout
            u = u3.transpose(0, 1, 2, 4, 3).reshape(Pn, T, 3 * nn, N)
            u = jnp.where(bkt["sign"], -u, u)
        with jax.named_scope("mv/contract"):
            v = jnp.einsum("tde,pten->ptdn", bkt["Ke"],
                           bkt["ck"][:, :, None, :] * u,
                           precision=ops.precision)
            v = jnp.where(bkt["sign"], -v, v)
        with jax.named_scope("mv/scatter"):
            rows = (v.reshape(Pn, T, nn, 3, N).transpose(0, 1, 2, 4, 3)
                    .reshape(Pn, T * nn * N, 3))
            ids = node.reshape(Pn, T * nn * N)
            y3 = jax.vmap(
                lambda yp, ip, rp: yp.at[ip].add(rp, mode="drop"))(
                    y3, ids, rows)
    with jax.named_scope("mv/scatter"):
        y = ops._apply_springs(data, x, y3.reshape(Pn, ops.n_loc))
    with jax.named_scope("mv/halo"):
        return ops.iface_assemble(data, y)
