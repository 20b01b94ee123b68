"""Structured-block fast path: gather/scatter-free matvec + ppermute halos.

TPU hardware has no vector gather/scatter — XLA lowers arbitrary indexed
reads/writes to near-serial code (measured ~28 ms/iter at 160k dofs vs
~0.4 ms for all dense work).  The TPU-native answer for the reference's
problem class: octree meshes are (collections of) structured blocks, and on a
structured block the element gather is EIGHT CONTIGUOUS SLICES of the
displacement grid and the scatter-add is eight shifted slice-adds — pure
dense memory traffic, with the per-cell ``ck`` heterogeneity kept as a cell
grid.  The element matmul stays the same (24x24) MXU einsum.

Domain decomposition: 1-D slabs along x, one slab per device.  Neighboring
slabs share one node plane; after the local matvec the two copies of a shared
plane hold partial sums which are combined by a single bidirectional
``lax.ppermute`` of boundary planes over the mesh axis — the direct analogue
of the reference's neighbor Isend/Recv halo exchange (pcg_solver.py:317-334)
riding ICI.

The vector/weight/eff/dot machinery and the whole PCG stack are shared with
the general unstructured path through the same ops protocol; only
matvec/diag/assembly differ.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from pcg_mpi_solver_tpu.models.element import HEX_CORNERS
from pcg_mpi_solver_tpu.models.model_data import ModelData
from pcg_mpi_solver_tpu.ops.matvec import Ops


@dataclasses.dataclass
class StructuredPartition:
    """Slab decomposition of a structured cube model (duck-compatible with
    the PartitionedModel fields the driver/export layer uses)."""

    n_parts: int
    n_loc: int                  # 3 * nxn_loc * nny * nnz
    n_iface: int                # unused (halo via ppermute); kept for protocol
    n_node_loc: int             # nxn_loc * nny * nnz
    glob_n_dof: int
    glob_n_dof_eff: int
    glob_n_node: int
    nxc: int                    # local cells along x (same for every part)
    ny: int
    nz: int

    ck: np.ndarray              # (P, nxc, ny, nz) cell stiffness scale
                                # (the device holds flat_cells(ck))
    ce: np.ndarray              # (P, nxc, ny, nz) cell strain scale (1/h)
    Ke: np.ndarray              # (24, 24)
    diag_Ke: np.ndarray         # (24,)
    Se: np.ndarray              # (6, 24)
    weight: np.ndarray          # (P, n_loc)
    node_weight: np.ndarray     # (P, n_node_loc)
    eff: np.ndarray             # (P, n_loc)
    F: np.ndarray               # (P, n_loc)
    Ud: np.ndarray              # (P, n_loc)
    dof_gid: np.ndarray         # (P, n_loc) int64
    node_gid: np.ndarray        # (P, n_node_loc) int64
    ndof_p: np.ndarray          # (P,)

    # Sharded setup (ISSUE 14): the slab range whose rows are populated;
    # (0, n_parts) for a full build.  The layout here is analytic (every
    # dimension derives from the grid), so there is no exchange — each
    # process just fills its own slab rows.
    part_range: Optional[tuple] = None


def partition_structured(model: ModelData, n_parts: int,
                         part_range=None) -> StructuredPartition:
    """Slab-partition a structured cube model (requires model.grid set and
    nx % n_parts == 0).  With ``part_range=(lo, hi)`` only those slabs'
    model-sized gathers (F/Ud/eff/ck/ce/gid maps) are materialized — the
    sharded-setup fast path; rows outside the range stay zero/-1."""
    from pcg_mpi_solver_tpu.parallel.partition import BUILD_CALLS

    BUILD_CALLS["partition_structured"] += 1
    if model.grid is None:
        raise ValueError("model has no structured-grid metadata")
    nx, ny, nz, _h = model.grid
    if nx % n_parts != 0:
        raise ValueError(f"nx={nx} not divisible by n_parts={n_parts}")
    if len(model.elem_lib) != 1 or 0 not in model.elem_lib:
        raise ValueError("structured path expects the single-type cube library")

    P = n_parts
    if part_range is None:
        part_range = (0, P)
    lo, hi = int(part_range[0]), int(part_range[1])
    if not (0 <= lo < hi <= P):
        raise ValueError(f"part_range {part_range} outside [0, {P})")
    local = range(lo, hi)
    nxc = nx // P
    nxn = nxc + 1
    nny, nnz = ny + 1, nz + 1
    n_loc = 3 * nxn * nny * nnz

    lib = model.elem_lib[0]

    # cell ck grid: global element id = ex + nx*(ey + ny*ez)  (x fastest)
    ck_glob = np.asarray(model.ck).reshape(nz, ny, nx).transpose(2, 1, 0)  # (nx,ny,nz)
    ck = np.zeros((P, nxc, ny, nz))
    ce = np.zeros((P, nxc, ny, nz))
    ce_glob = np.asarray(model.ce).reshape(nz, ny, nx).transpose(2, 1, 0)
    for p in local:
        ck[p] = ck_glob[p * nxc:(p + 1) * nxc]
        ce[p] = ce_glob[p * nxc:(p + 1) * nxc]

    # local node (ix,iy,iz) [x-major local layout] -> global dof ids
    nnx = nx + 1
    weight = np.zeros((P, n_loc))
    eff = np.zeros((P, n_loc))
    F = np.zeros((P, n_loc))
    Ud = np.zeros((P, n_loc))
    # -1 init so non-built rows of a sharded build read as padding for
    # the owner masks (a full build overwrites every row — bit-identical)
    dof_gid = np.full((P, n_loc), -1, dtype=np.int64)

    eff_mask_glob = np.zeros(model.n_dof, dtype=bool)
    eff_mask_glob[model.dof_eff] = True

    n_node_loc = nxn * nny * nnz
    node_gid = np.full((P, n_node_loc), -1, dtype=np.int64)
    ix = np.arange(nxn)
    iy = np.arange(nny)
    iz = np.arange(nnz)
    IX, IY, IZ = np.meshgrid(ix, iy, iz, indexing="ij")
    for p in local:
        gnode = (IX + p * nxc) + nnx * (IY + nny * IZ)          # (nxn,nny,nnz)
        node_gid[p] = gnode.reshape(-1)
        gdof = (3 * gnode[..., None] + np.arange(3)).transpose(3, 0, 1, 2)
        # local flat layout: (c, ix, iy, iz) row-major
        g = gdof.reshape(-1)
        dof_gid[p] = g
        F[p] = model.F[g]
        Ud[p] = model.Ud[g]
        eff[p] = eff_mask_glob[g].astype(float)
    # ownership: the lowest part containing a dof keeps weight 1 (same rule
    # as the unstructured path / reference partition_mesh.py:885-887) — a
    # shared plane belongs to the lower slab, so zero the lower plane of
    # every part except the first.
    weight = np.ones((P, 3, nxn, nny, nnz))
    weight[1:, :, 0] = 0.0
    weight = weight.reshape(P, n_loc)
    node_weight = np.ones((P, nxn, nny, nnz))
    node_weight[1:, 0] = 0.0
    node_weight = node_weight.reshape(P, n_node_loc)

    return StructuredPartition(
        n_parts=P,
        n_loc=n_loc,
        n_iface=0,
        n_node_loc=n_node_loc,
        glob_n_dof=model.n_dof,
        glob_n_dof_eff=len(model.dof_eff),
        glob_n_node=model.n_node,
        nxc=nxc, ny=ny, nz=nz,
        ck=ck,
        ce=ce,
        Ke=np.asarray(lib["Ke"], np.float64),
        diag_Ke=np.asarray(lib["diagKe"], np.float64),
        Se=np.asarray(lib["Se"], np.float64),
        weight=weight,
        node_weight=node_weight,
        eff=eff,
        F=F,
        Ud=Ud,
        dof_gid=dof_gid,
        node_gid=node_gid,
        ndof_p=np.full(P, n_loc),
        part_range=(lo, hi),
    )


def flat_cells(ck: np.ndarray) -> np.ndarray:
    """(P, cx, ny, nz) cell grid -> (P, cx, ny*(nz+1) - 1) cell plane.

    The stencil runs on the node grid with its (y, z) plane flattened,
    ``(P, 3, nxn, nny*nnz)``; cell (iy, iz) then sits at ``iy*nnz + iz``
    of the flat plane, and the slot ``iz == nz`` between two rows is an
    inert ghost column that holds 0.0, so it contributes exact zeros."""
    ck = np.asarray(ck)
    P, cx = ck.shape[:2]
    pad = np.pad(ck, ((0, 0), (0, 0), (0, 0), (0, 1)))
    return pad.reshape(P, cx, -1)[:, :, :-1]


def device_data_structured(sp: StructuredPartition, dtype=jnp.float64) -> dict:
    """The slab stencil's data as host arrays (see
    ``ops.matvec.device_data``).  ``ck`` is held once, in the flat-plane
    form of :func:`flat_cells`; ``ce`` (export only) keeps the cell grid."""
    return {
        "blocks": [{
            "Ke": np.asarray(sp.Ke, dtype),
            "diag_Ke": np.asarray(sp.diag_Ke, dtype),
            "Se": np.asarray(sp.Se, dtype),
            "ck": np.asarray(flat_cells(sp.ck), dtype),
            "ce": np.asarray(sp.ce, dtype),
        }],
        "weight": np.asarray(sp.weight, dtype),
        "node_weight": np.asarray(sp.node_weight, dtype),
        "eff": np.asarray(sp.eff, dtype),
        "F": np.asarray(sp.F, dtype),
        "Ud": np.asarray(sp.Ud, dtype),
    }


# Corner offsets in the element-dof ordering of models/element.py
_CORNERS = HEX_CORNERS.astype(np.int64)  # (8, 3)


VALID_FORMS = ("gse", "gsplit", "corner")

# Declared collective cost of one _halo exchange on a sharded multi-part
# slab: one bidirectional plane swap = 2 ppermutes per matvec.  Part of
# StructuredOps.body_collective_budget — the contract the analysis/
# collective-budget rule proves against the traced PCG body jaxpr.
STENCIL_HALO_PPERMUTES = 2


def matvec_form() -> str:
    """The PCG_TPU_MATVEC_FORM knob, validated — the ONE place its
    name/default/valid values live (resolved once at stencil-ops
    construction; reported by bench.py and checkpoint fingerprints)."""
    import os

    form = os.environ.get("PCG_TPU_MATVEC_FORM", "gse")
    if form not in VALID_FORMS:
        raise ValueError(
            f"PCG_TPU_MATVEC_FORM must be one of {VALID_FORMS}, got {form!r}")
    return form


def corner_matvec_grid(Ke, ck, xg):
    """Fusion-friendly brick-grid matvec: no (24, cells) intermediates.

    y = sum_b pad_b(sum_a Ke[3b:3b+3, 3a:3a+3] @ (ck * x_a)) with each
    3x3 block unrolled to scalar-broadcast FMAs (static unroll — XLA
    fuses the whole thing into slice-read -> FMA -> pad-accumulate
    chains), landing on the node grid as zero-padded translates.  Shared
    by the structured slab backend (_gse corner form) and the hybrid
    level-grid stencil.

    Ke (24, 24); ck (P, cx, cy, cz); xg (P, 3, cx+1, cy+1, cz+1)."""
    cx, cy, cz = ck.shape[1], ck.shape[2], ck.shape[3]
    w = []
    for a in range(8):
        dx, dy, dz = _CORNERS[a]
        with jax.named_scope("mv/gather"):
            xa = xg[:, :, dx:dx + cx, dy:dy + cy, dz:dz + cz]
        with jax.named_scope("mv/contract"):
            w.append(ck[:, None] * xa)
    y = None
    for b in range(8):
        ex, ey, ez = _CORNERS[b]
        with jax.named_scope("mv/contract"):
            comps = []
            for d in range(3):
                acc = None
                for a in range(8):
                    for c in range(3):
                        t = Ke[3 * b + d, 3 * a + c] * w[a][:, c]
                        acc = t if acc is None else acc + t
                comps.append(acc)
            vb = jnp.stack(comps, axis=1)              # (P, 3, cells)
        with jax.named_scope("mv/scatter"):
            term = jnp.pad(vb, ((0, 0), (0, 0), (ex, 1 - ex),
                                (ey, 1 - ey), (ez, 1 - ez)))
            y = term if y is None else y + term
    return y


def gsplit_matvec_grid(Ke, ck, xg, precision):
    """gse minus the gather CONCAT (PCG_TPU_MATVEC_FORM=gsplit):
    v = sum_a Ke[:, 3a:3a+3] @ (ck * x_a) accumulates eight
    (24,3)@(3,cells) einsums whose inputs are contiguous grid slices —
    the (24, cells) gathered array u never exists, saving one full HBM
    round-trip of it (~650 MB at 10M dofs) against gse.  Keeps gse's
    single (24, cells) product; the caller scatters it.  Shared by the
    structured slab backend and the hybrid level-grid stencil (like
    corner_matvec_grid).

    Ke (24, 24); ck (P, cx, cy, cz); xg (P, 3, cx+1, cy+1, cz+1);
    returns v (P, 24, cx, cy, cz) in 3*corner + comp dof order."""
    cx, cy, cz = ck.shape[1], ck.shape[2], ck.shape[3]
    v = None
    for a in range(8):
        dx, dy, dz = _CORNERS[a]
        with jax.named_scope("mv/gather"):
            xa = xg[:, :, dx:dx + cx, dy:dy + cy, dz:dz + cz]
        with jax.named_scope("mv/contract"):
            t = jnp.einsum("dc,pcxyz->pdxyz", Ke[:, 3 * a:3 * a + 3],
                           ck[:, None] * xa, precision=precision)
            v = t if v is None else v + t
    return v


@dataclasses.dataclass(frozen=True)
class StructuredOps(Ops):
    """Same operator protocol as Ops, slab-structured implementation."""

    nxc: int = 0
    ny: int = 0
    nz: int = 0
    n_parts: int = 1
    # cells above which f64 matvecs run x-slab-chunked (see _chunk_planes)
    chunk_threshold: int = 500_000
    # f32 matvecs through the fused Pallas plane-march kernel
    # (ops/pallas_matvec.py) instead of the XLA gather/einsum/scatter
    use_pallas: bool = False
    # run the kernel through the Pallas interpreter (CI on CPU exercises
    # the real solver->kernel dispatch; SolverConfig.pallas='interpret')
    pallas_interpret: bool = False
    # XLA stencil formulation, PINNED at construction (the checkpoint
    # fingerprint records it; an env flip after construction must not
    # silently change what a resume replays)
    form: str = "gse"

    def __post_init__(self):
        # explicit pins (incl. dataclasses.replace) must not bypass the
        # validation matvec_form() applies to the env path — a typo'd
        # form would silently run gse while being recorded as itself
        if self.form not in VALID_FORMS:
            raise ValueError(
                f"form must be one of {VALID_FORMS}, got {self.form!r}")

    @classmethod
    def from_partition(cls, sp: StructuredPartition, dot_dtype=jnp.float64,
                       axis_name=None, precision=jax.lax.Precision.HIGHEST,
                       use_pallas=False, form=None, pallas_interpret=False):
        return cls(n_loc=sp.n_loc, n_iface=0,
                   n_node_loc=sp.n_node_loc, n_node_iface=0,
                   dot_dtype=dot_dtype,
                   axis_name=axis_name, precision=precision,
                   nxc=sp.nxc, ny=sp.ny, nz=sp.nz, n_parts=sp.n_parts,
                   use_pallas=use_pallas, pallas_interpret=pallas_interpret,
                   form=form if form is not None else matvec_form())

    # -- grid helpers ---------------------------------------------------
    def _grid(self, x):
        Pl = x.shape[0]
        return x.reshape(Pl, 3, self.nxc + 1, self.ny + 1, self.nz + 1)

    def _plane(self, x):
        """(Pl, n_loc) -> (Pl, 3, nxn, nny*nnz): the node grid with its
        (y, z) plane flattened — a pure reshape of the (c, ix, iy, iz)
        layout, so the plane is the minor (lane) axis and pads by at
        most 127 slots where a z row of nnz nodes pads to 128 or 256."""
        Pl = x.shape[0]
        return x.reshape(Pl, 3, self.nxc + 1, (self.ny + 1) * (self.nz + 1))

    def _ck_grid(self, ck):
        """Flat cell plane (Pl, cx, [1,] Lc) -> (Pl, cx, ny, nz) cell grid
        (drops the ghost column; see flat_cells)."""
        Pl, cx = ck.shape[0], ck.shape[1]
        full = jnp.pad(ck.reshape(Pl, cx, -1), ((0, 0), (0, 0), (0, 1)))
        return full.reshape(Pl, cx, self.ny, self.nz + 1)[..., :self.nz]

    def _corner_shifts(self):
        """Flat-plane offset ``dy*nnz + dz`` of each element corner, and
        the plane lengths (Ln nodes, Lc = ny*nnz - 1 cell slots)."""
        nnz = self.nz + 1
        shifts = [int(dy * nnz + dz) for _dx, dy, dz in _CORNERS]
        return shifts, (self.ny + 1) * nnz, self.ny * nnz - 1

    def _gather_plane(self, xp):
        """(Pl,3,cx+1,Ln) -> (Pl,24,cx,Lc): corner a of the cell at flat
        slot l is node l + dy*nnz + dz, so the gather is 8 contiguous
        windows of the plane (cx from the node slab, so x-slab chunks run
        through the same code)."""
        cx = xp.shape[2] - 1
        shifts, _ln, lc = self._corner_shifts()
        slots = [xp[:, :, dx:dx + cx, s:s + lc]
                 for (dx, _dy, _dz), s in zip(_CORNERS, shifts)]
        return jnp.concatenate(slots, axis=1)  # dof order: 3*corner + comp

    def _scatter_plane(self, v):
        """(Pl,24,cx,Lc) -> (Pl,3,cx+1,Ln): the sum of 8 zero-padded
        translates of the plane, in _scatter_cells' order."""
        shifts, ln, lc = self._corner_shifts()
        y = None
        for a, ((dx, _dy, _dz), s) in enumerate(zip(_CORNERS, shifts)):
            t = jnp.pad(v[:, 3 * a:3 * a + 3],
                        ((0, 0), (0, 0), (dx, 1 - dx), (s, ln - s - lc)))
            y = t if y is None else y + t
        return y

    def stencil_lane_fill(self) -> float:
        """Real cells over lane-padded slots of the XLA stencil's (24,
        cells) arrays, whose minor axis the TPU pads to a multiple of 128
        lanes: the flat plane (gse) pads ny*(nz+1) - 1 slots, of which the
        ghost column is unused too; the 5D grid (corner, gsplit) pads each
        z row of nz cells."""
        lanes = lambda n: -(-n // 128) * 128
        if self.form == "gse":
            return self.ny * self.nz / lanes(self.ny * (self.nz + 1) - 1)
        return self.nz / lanes(self.nz)

    def _gather_cells(self, xg):
        """(Pl,3,cx+1,cy+1,cz+1) -> (Pl,24,cx,cy,cz) via 8 contiguous
        slices (cell shape derived from the node grid, so x-slab chunks
        work through the same code)."""
        cx, cy, cz = xg.shape[2] - 1, xg.shape[3] - 1, xg.shape[4] - 1
        slots = []
        for a in range(8):
            dx, dy, dz = _CORNERS[a]
            s = xg[:, :, dx:dx + cx, dy:dy + cy, dz:dz + cz]
            slots.append(s)
        return jnp.concatenate(slots, axis=1)  # dof order: 3*corner + comp

    def _scatter_cells(self, v):
        """(Pl,24,cx,cy,cz) -> (Pl,3,cx+1,cy+1,cz+1) via a sum of 8
        zero-padded translates (one fused output pass; an .at[].add chain
        would serialize 8 read-modify-write sweeps of the node grid)."""
        terms = []
        for a in range(8):
            dx, dy, dz = _CORNERS[a]
            terms.append(jnp.pad(
                v[:, 3 * a:3 * a + 3],
                ((0, 0), (0, 0), (dx, 1 - dx), (dy, 1 - dy), (dz, 1 - dz))))
        y = terms[0]
        for t in terms[1:]:
            y = y + t
        return y

    def body_collective_budget(self, variant: str = "classic",
                               precond: str = "jacobi") -> dict:
        """Structured-slab collective contract of the PCG loop body: the
        scalar psums + deferred-check psum from the base table (no iface
        psum — n_iface is 0 by construction; boundary planes combine via
        _halo instead), plus the halo exchange's ``STENCIL_HALO_PPERMUTES``
        ppermutes per matvec.  Proven against the traced body jaxpr by the
        analysis/ collective-budget rule — a stencil change that adds
        shifts must update the declaration consciously.

        ``precond="mg"`` multiplies the halo count by the V-cycle's
        fine-level matvecs (1 body matvec + 2*mg_degree cycle matvecs,
        each = one halo exchange) and the base budget already carries
        the restriction psum (ops/matvec.precond_cycle_cost — one
        table for gauges, budget and proof)."""
        from pcg_mpi_solver_tpu.ops.matvec import precond_cycle_cost

        budget = dict(super().body_collective_budget(variant, precond))
        if self.n_parts > 1 and self.axis_name is not None:
            mv_extra, _ps = precond_cycle_cost(precond, self.mg_degree)
            budget["ppermute"] = STENCIL_HALO_PPERMUTES * (1 + mv_extra)
        return budget

    def _halo(self, yg):
        """Combine partial sums on shared slab-boundary planes: one
        bidirectional ppermute of x planes — (3, nny*nnz) on the flat
        plane, (3, nny, nnz) on the 5D grid — over the mesh axis."""
        P = self.n_parts
        if P == 1:
            return yg
        if self.axis_name is None:
            # unsharded multi-part view (testing): roll over leading axis
            up = yg[:, :, -1]
            dn = yg[:, :, 0]
            from_left = jnp.roll(up, 1, axis=0).at[0].set(0.0)
            from_right = jnp.roll(dn, -1, axis=0).at[-1].set(0.0)
            yg = yg.at[:, :, 0].add(from_left)
            yg = yg.at[:, :, -1].add(from_right)
            return yg
        idx = jax.lax.axis_index(self.axis_name)
        up = yg[:, :, -1]
        dn = yg[:, :, 0]
        fwd = [(i, (i + 1) % P) for i in range(P)]
        bwd = [(i, (i - 1) % P) for i in range(P)]
        from_left = jax.lax.ppermute(up, self.axis_name, fwd)
        from_right = jax.lax.ppermute(dn, self.axis_name, bwd)
        from_left = jnp.where(idx == 0, 0.0, from_left)
        from_right = jnp.where(idx == P - 1, 0.0, from_right)
        yg = yg.at[:, :, 0].add(from_left)
        yg = yg.at[:, :, -1].add(from_right)
        return yg

    # -- operator protocol ---------------------------------------------
    def _chunk_planes(self, dtype) -> int:
        """x-slab chunk size for the sequential matvec, or 0 for one shot.

        f64 arithmetic on TPU is software-emulated (several f32 passes per
        op); unchunked at 10M dofs the f64 (24, cells) gather/product
        intermediates need multi-GB temp buffers.  f64 matvecs are rare
        (Dirichlet lifting + one true-residual per refinement cycle), so a
        fori_loop over x-slabs trades a little latency for bounded memory.
        The body is the same gather/einsum/scatter as the one-shot path —
        f64 conv lowerings proved pathological on real v5e (the remote
        compile never returned), while the f64 einsum path is routinely
        exercised; see bench history r01-r02."""
        cells = self.nxc * self.ny * self.nz
        if np.dtype(dtype) != np.float64 or cells < self.chunk_threshold:
            return 0
        target = max(1, int(self.chunk_threshold / max(self.ny * self.nz, 1)))
        # largest divisor of nxc that is <= target
        for c in range(min(target, self.nxc), 0, -1):
            if self.nxc % c == 0:
                return c if c < self.nxc else 0
        return 0

    def _gse(self, blk, xp, ck):
        """One slab matvec on the flat plane: xp (Pl, 3, cx+1, Ln) ->
        (Pl, 3, cx+1, Ln); ck (Pl, cx, 1, Lc) is the flat cell plane as a
        one-row (y, z) grid, so it keeps the cell grid's (P, x, y, z)
        axes.  Three XLA formulations, env-selected.

        - ``gse`` (default): gather -> one (24,24)@(24,cells) MXU einsum
          -> scatter, all on the flat plane, whose (24, cells) arrays fill
          ~99% of their lanes where a z row of 96 (150) cells fills 75%
          (59%).  Materializes the gathered corner array and the
          product — two (24, cells) HBM round-trips (~650 MB each way at
          10M dofs).
        - ``corner`` (PCG_TPU_MATVEC_FORM=corner): per-output-corner
          accumulation y = sum_b pad_b(sum_a Ke[3b:3b+3, 3a:3a+3] @
          (ck * x_a)), with each 3x3 block unrolled to scalar
          multiply-adds so XLA fuses the whole thing into
          slice-read -> FMA -> pad-accumulate chains and NO (24, cells)
          intermediate ever exists.  Trades the single big MXU matmul
          (arithmetic intensity ~12 flops/byte — far below the MXU
          roofline anyway; the op is HBM-bound) for ~4x less HBM
          traffic.  The knob is resolved ONCE at ops construction
          (self.form) — toggling the env later does nothing.
        - ``gsplit``: see gsplit_matvec_grid.

        corner and gsplit run on the 5D grid view of xp and ck.
        """
        if self.form != "gse":
            Pl, cx = xp.shape[0], xp.shape[2] - 1
            xg = xp.reshape(Pl, 3, cx + 1, self.ny + 1, self.nz + 1)
            ckg = self._ck_grid(ck)
            fn = self._gse_corner if self.form == "corner" else self._gse_split
            return fn(blk, xg, ckg).reshape(xp.shape)
        ck = ck.reshape(ck.shape[0], ck.shape[1], -1)  # (P, cx, Lc)
        with jax.named_scope("mv/gather"):
            u = self._gather_plane(xp)                 # (P, 24, cx, Lc)
        with jax.named_scope("mv/contract"):
            v = jnp.einsum("de,pexl->pdxl", blk["Ke"], ck[:, None] * u,
                           precision=self.precision)
        with jax.named_scope("mv/scatter"):
            return self._scatter_plane(v)

    def _gse_corner(self, blk, xg, ck):
        return corner_matvec_grid(blk["Ke"], ck, xg)

    def _gse_split(self, blk, xg, ck):
        v = gsplit_matvec_grid(blk["Ke"], ck, xg, self.precision)
        with jax.named_scope("mv/scatter"):
            return self._scatter_cells(v)

    def matvec_local(self, data, x):
        """Part-local K.x on the slab grid, in the ``mv/gather``,
        ``mv/contract`` and ``mv/scatter`` stages of Ops.matvec_local
        (the fused Pallas kernel is one ``mv/contract``)."""
        if x.ndim == 3:
            # RHS-block axis (Ops.matvec contract): the stencil is built
            # around grid reshapes of one flat vector, so the block is
            # batched with vmap — XLA turns the slice/einsum/pad chain
            # into its batched twin; no per-column Python loop.
            return jax.vmap(lambda xc: self.matvec_local(data, xc),
                            in_axes=-1, out_axes=-1)(x)
        return self._matvec_plane(data, x).reshape(x.shape)

    def _matvec_plane(self, data, x):
        """Part-local K.x of one (Pl, n_loc) vector, on the flat plane
        (Pl, 3, nxn, nny*nnz)."""
        blk = data["blocks"][0]
        with jax.named_scope("mv/gather"):
            xp = self._plane(x)                        # (P, 3, nxn, Ln)
        chunk = self._chunk_planes(x.dtype)
        if (self.use_pallas and chunk == 0
                and np.dtype(x.dtype) == np.float32):
            from pcg_mpi_solver_tpu.ops.pallas_matvec import (
                batched_structured_matvec)

            with jax.named_scope("mv/contract"):
                y = batched_structured_matvec(
                    self._grid(x), self._ck_grid(blk["ck"]), blk["Ke"],
                    interpret=self.pallas_interpret)
            return y.reshape(xp.shape)
        if chunk == 0:
            # slice-gather + einsum: contiguous slices, MXU matmul, shifted
            # slice-adds — no vector gather/scatter anywhere.
            return self._gse(blk, xp, blk["ck"][:, :, None])

        Pl, ln = xp.shape[0], xp.shape[3]
        lc = blk["ck"].shape[2]
        n_chunks = self.nxc // chunk

        def body(i, y):
            a = i * chunk
            with jax.named_scope("mv/gather"):
                xs = jax.lax.dynamic_slice(
                    xp, (0, 0, a, 0), (Pl, 3, chunk + 1, ln))
                cks = jax.lax.dynamic_slice(
                    blk["ck"], (0, a, 0), (Pl, chunk, lc))
            ys = self._gse(blk, xs, cks[:, :, None])
            with jax.named_scope("mv/scatter"):
                cur = jax.lax.dynamic_slice(y, (0, 0, a, 0), ys.shape)
                return jax.lax.dynamic_update_slice(y, cur + ys,
                                                    (0, 0, a, 0))

        with jax.named_scope("mv/scatter"):
            y0 = jnp.zeros_like(xp)
        return jax.lax.fori_loop(0, n_chunks, body, y0)

    def matvec(self, data, x):
        if x.ndim == 3:
            return jax.vmap(lambda xc: self.matvec(data, xc),
                            in_axes=-1, out_axes=-1)(x)
        yp = self._matvec_plane(data, x)
        with jax.named_scope("mv/halo"):
            return self._halo(yp).reshape(x.shape)

    def diag_local(self, data):
        blk = data["blocks"][0]
        Pl = blk["ck"].shape[0]
        v = blk["diag_Ke"][None, :, None, None] * blk["ck"][:, None]
        return self._scatter_plane(v).reshape(Pl, self.n_loc)

    def diag(self, data):
        yp = self._plane(self.diag_local(data))
        return self._halo(yp).reshape(-1, self.n_loc)

    # -- node-block (3x3) diagonal for block-Jacobi ---------------------
    def node_block_diag(self, data):
        """Per-node 3x3 blocks as 9 channels on the node grid: for corner
        ``a`` every cell adds ``ck * Ke[3a:3a+3, 3a:3a+3]`` to its corner
        node — the same 8 pad-translates as diag_local, 9-channel; slab-
        boundary planes assemble through the halo like any other field."""
        from pcg_mpi_solver_tpu.ops.precond import corner_block_field

        blk = data["blocks"][0]
        ck = self._ck_grid(blk["ck"])                     # (P, cx, cy, cz)
        Pl = ck.shape[0]
        g = self._halo(corner_block_field(blk["Ke"], ck, _CORNERS))
        return g.reshape(Pl, 9, self.n_node_loc) \
            .transpose(0, 2, 1).reshape(Pl, self.n_node_loc, 3, 3)

    def _as_node3(self, v):
        # structured dof layout is component-major: (P, 3, nodes[, R])
        if v.ndim == 3:
            return v.reshape(v.shape[0], 3, self.n_node_loc,
                             v.shape[2]).transpose(0, 2, 1, 3)
        return v.reshape(v.shape[0], 3, self.n_node_loc).transpose(0, 2, 1)

    def _from_node3(self, z3):
        if z3.ndim == 4:
            return z3.transpose(0, 2, 1, 3).reshape(
                z3.shape[0], self.n_loc, z3.shape[3])
        return z3.transpose(0, 2, 1).reshape(z3.shape[0], self.n_loc)

    def iface_assemble(self, data, y):
        if y.ndim == 3:
            return jax.vmap(lambda yc: self.iface_assemble(data, yc),
                            in_axes=-1, out_axes=-1)(y)
        return self._halo(self._plane(y)).reshape(y.shape)

    # -- export path ----------------------------------------------------
    def _node_grid(self, y):
        Pl = y.shape[0]
        return y.reshape(Pl, -1, self.nxc + 1, self.ny + 1, self.nz + 1)

    def elem_strain(self, data, x):
        blk = data["blocks"][0]
        u = self._gather_cells(self._grid(x))                  # (P,24,cx,cy,cz)
        eps = jnp.einsum("sd,pdxyz->psxyz", blk["Se"],
                         blk["ce"][:, None] * u, precision=self.precision)
        Pl = eps.shape[0]
        return [eps.reshape(Pl, 6, -1)]

    def elem_scale(self, data):
        blk = data["blocks"][0]
        Pl = blk["ck"].shape[0]
        return [(self._ck_grid(blk["ck"]) * blk["ce"]).reshape(Pl, -1)]

    def nodal_average(self, data, vals_list):
        """Cell values -> averaged nodal grid via 8 shifted slice-adds of
        sums and counts, halo'd as extra channels."""
        vals = vals_list[0]
        Pl, k = vals.shape[0], vals.shape[1]
        nxc, ny, nz = self.nxc, self.ny, self.nz
        vg = vals.reshape(Pl, k, nxc, ny, nz)
        cg = jnp.ones((Pl, 1, nxc, ny, nz), vals.dtype)
        both = jnp.concatenate([vg, cg], axis=1)               # (P, k+1, cells)
        y = None
        for a in range(8):
            dx, dy, dz = _CORNERS[a]
            t = jnp.pad(both, ((0, 0), (0, 0), (dx, 1 - dx),
                               (dy, 1 - dy), (dz, 1 - dz)))
            y = t if y is None else y + t
        y = self._halo(y)
        avg = y[:, :k] / (y[:, k:] + 1e-15)
        return avg.reshape(Pl, k, -1)
