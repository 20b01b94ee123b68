"""Hybrid level-grid backend: octree meshes as sparse unions of structured
grids.

The reference's problem class is octree meshes (2:1-graded hexahedral cells,
<=144 geometric pattern types; partition_mesh.py:420-493, 1074).  On TPU the
pain point is the per-element gather/scatter: every vector gather costs an
order of magnitude more than the dense math it feeds.  But in any graded
octree the overwhelming majority of cells are pure 8-node "bricks" of some
refinement level — only the level-interface transition cells carry hanging
nodes.  This backend:

- places each level's brick cells on a DENSE per-level cell grid over the
  part's bounding box, with ``ck = 0`` holes wherever this level has no
  brick (a zero-stiffness cell contributes exactly nothing, so holes are
  free);
- gathers each level's NODE lattice once per matvec (one (n,3)-row gather
  per level — ~8x less gather traffic than per-element corner gathers),
  runs the same slice-gather -> MXU einsum -> padded-translate-scatter
  stencil as the structured backend (parallel/structured.py);
- keeps ONLY transition cells in the general type blocks (ops/matvec.py)
  — they are excluded from the type blocks via
  ``partition_model(block_filter=...)``;
- accumulates the level lattices' live slots and the transition
  elements' node rows into the local dof vector through ONE
  partition-composed gather combine (CombineMaps), with no row scatter;
- shares everything else (interface psum assembly, weighted dots, PCG,
  exports) with the general backend through the same Ops protocol.

Correctness note: a lattice point of a level grid that is NOT a node of the
mesh (or not local to the part) maps to the pad index — its gathered value
(0) only ever multiplies into cells with ck = 0, and its scattered output
row is dropped, because every corner of a ck > 0 brick cell IS a local mesh
node by construction.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from pcg_mpi_solver_tpu.models.model_data import ModelData
from pcg_mpi_solver_tpu.ops.matvec import Ops, device_data
from pcg_mpi_solver_tpu.parallel.partition import (
    PartitionedModel, make_elem_part, partition_model)


@dataclasses.dataclass
class LevelGrid:
    """One refinement level's brick cells as a BATCH of dense blocks.

    A graded octree's per-level bounding box is mostly holes at scale
    (measured 3.7% fill on the 5.67M-dof flagship's finest level — 96%
    of a dense-bbox stencil would be wasted compute), so each level is
    tiled into bs^3-cell blocks and only blocks containing at least one
    brick are kept (5.8x total-cell reduction on that flagship at
    bs=8).  Small or well-filled levels keep a single dense-bbox block
    (nb == 1, dims == bbox) — the tiled and dense layouts are the same
    code path with different dims.

    Parts are padded to a common block count nb; padding blocks have
    ck = 0 and nidx = pad, so they compute and scatter exactly nothing.
    """

    size: int                   # cell edge length in finest lattice units
                                # (0 = merged multi-size batch, see
                                # PCG_TPU_HYBRID_MERGE in partition_hybrid)
    nb: int                     # blocks per part (common, padded)
    bx: int                     # per-BLOCK cell dims
    by: int
    bz: int
    origin: np.ndarray          # (P, nb, 3) block origin in LEVEL units
    ck: np.ndarray              # (P, nb, bx, by, bz); 0 = hole
    ce: np.ndarray              # (P, nb, bx, by, bz)
    nidx: np.ndarray            # (P, nb, (bx+1)*(by+1)*(bz+1)) int32 local
                                # node ids, n_node_loc = pad
    n_cells: np.ndarray         # (P,) true brick count per part


@dataclasses.dataclass
class CombineMaps:
    """Slot->node gather-combine maps (the scatter-free node combine).

    A 2026-07-30 chip session measured the duplicate-row scatter at
    88.7 ns/row against 5.9 ns/row gathers — so the combine is
    transposed: every element->node contribution row (level-grid lattice
    slots and transition-element rows alike) is sorted by target node at
    PARTITION time and composed into direct per-node source-slot indices.
    At solve time the element->node accumulation (reference
    pcg_solver.py:300's bincount) is gathers only: KD dense row gathers
    for every node, KE row gathers for the H heavy nodes, and one gather
    that adds each heavy node's sum back by its ``hpos``.

    Slot numbering: levels in list order, each level flat over
    (block, lattice pos) exactly as its ``nidx``; then, when the
    transition blocks join the combine, their (node slot, element) rows
    in block order as ``Ops.matvec_local`` builds them — runtime row
    arrays are concatenated in the same order, with ONE trailing zero row
    at index ``n_slots`` serving as the universal pad target.  A lattice
    slot that touches no ck != 0 cell of its block only ever carries an
    exact zero and is left out of the maps (``n_dropped``).
    """

    n_slots: int                # level slots + transition rows (zero row = pad)
    gidx: np.ndarray            # (P, KD, n_node_loc) int32 slot ids
    hgidx: np.ndarray           # (P, KE, H) int32 slot ids of the heavy rest
    hpos: np.ndarray            # (P, n_node_loc) int32 heavy row (H = none)
    n_dropped: int              # lattice slots of real nodes left out, all parts

    @property
    def kd(self) -> int:
        return int(self.gidx.shape[1])

    @property
    def rows_per_matvec(self) -> int:
        """Rows one combine gathers per part: KD*N dense, KE*H heavy,
        and N for the heavy add-back (none without heavy nodes)."""
        KE, H = self.hgidx.shape[1:]
        N = self.gidx.shape[2]
        return self.kd * N + (KE * H + N if H else 0)

    def gauges(self) -> Dict[str, int]:
        return {"hybrid.combine_rows_per_matvec": self.rows_per_matvec,
                "hybrid.combine_kd": self.kd,
                "hybrid.combine_heavy_nodes": int(self.hgidx.shape[2]),
                "hybrid.combine_slots_dropped": int(self.n_dropped)}


@dataclasses.dataclass
class HybridPartition:
    """PartitionedModel (transition cells only in its type blocks) plus the
    per-level brick grids.  Duck-compatible with the driver's pm usage."""

    pm: PartitionedModel
    levels: List[LevelGrid]
    brick_Ke: np.ndarray        # (24, 24) unit brick stiffness
    brick_diag: np.ndarray      # (24,)
    brick_Se: Optional[np.ndarray]  # (6, 24)
    combine: Optional[CombineMaps] = None

    def __getattr__(self, name):
        # Guard 'pm' and dunders: during unpickling/deepcopy the object
        # exists before __dict__ is populated, and delegating those lookups
        # would recurse.
        if name == "pm" or name.startswith("__"):
            raise AttributeError(name)
        return getattr(self.pm, name)


def can_hybrid(model: ModelData) -> bool:
    """Single source of truth for hybrid-backend eligibility (used by the
    quasi-static driver, the dynamics solver, and partition_hybrid)."""
    return (model.octree is not None
            and model.octree.get("brick_type") is not None)


def partition_hybrid(model: ModelData, n_parts: int,
                     elem_part: Optional[np.ndarray] = None,
                     method: str = "rcb") -> HybridPartition:
    from pcg_mpi_solver_tpu.parallel.partition import BUILD_CALLS

    BUILD_CALLS["partition_hybrid"] += 1
    if not can_hybrid(model):
        raise ValueError("model has no octree/brick metadata for the "
                         "hybrid backend")
    meta = model.octree
    bt = meta["brick_type"]
    leaves = np.asarray(meta["leaves"])
    # node_keys[i] = lattice key of node id i.  Generator-built models
    # number nodes in sorted-key order, but RECONSTRUCTED metadata
    # (models/octree.py reconstruct_lattice_meta) follows the bundle's
    # own numbering — sort once and keep the id permutation.
    raw_keys = np.asarray(meta["node_keys"])
    key_order = np.argsort(raw_keys)
    node_keys = raw_keys[key_order]
    sy, sz = meta["strides"]
    corners = np.asarray(meta["brick_corners"], dtype=np.int64)   # (8, 3)
    if not np.array_equal(corners, _CORNERS):
        raise ValueError("brick corner order does not match the level-grid "
                         "stencil's corner order")

    brick = model.elem_type == bt
    if elem_part is None:
        elem_part = make_elem_part(model, n_parts, method=method)
    pm = partition_model(model, n_parts, elem_part=elem_part,
                         block_filter=~brick)

    P = n_parts
    lib = model.elem_lib[bt]
    knobs = partition_env_knobs()   # one owner for the defaults
    bs_knob = knobs["block"]
    # PCG_TPU_HYBRID_MERGE (default OFF): give EVERY level the same tile
    # dims and merge all levels into ONE block batch after the loop —
    # legal because the stencil math is size-independent (level size
    # enters only through nidx and ck), and slot numbering is the same
    # level-order concatenation CombineMaps already uses.  Measured
    # chiplessly at the 5.67M-dof flagship (2026-07-31): the merge makes
    # COMPILE WORSE, not better (inner-cycle 473 -> 551 s, f64 amul
    # 999 -> 1328 s — the larger uniform batch outweighs the removed
    # per-level unroll), so it stays an off-by-default runtime A/B
    # candidate (1 launch vs 5 per matvec; parity-asserted in
    # tests/test_hybrid.py::test_merged_levels_match_unmerged).
    merge = knobs["merge"]
    sizes = sorted(int(v) for v in np.unique(leaves[brick, 3]))
    level_sel = []
    for s in sizes:
        sel_lvl = brick & (leaves[:, 3] == s)
        per_part = [np.where(sel_lvl & (elem_part == p))[0]
                    for p in range(P)]
        # level-unit cell coords (octree cells of size s are s-aligned)
        lat = [leaves[e, :3] // s for e in per_part]
        level_sel.append((s, per_part, lat))
    bs_eff = bs_knob
    if merge:
        # shared tile edge: cap the knob by the largest per-part level
        # extent so a force-dense setting (e.g. 10^6) cannot allocate an
        # astronomically-sized tile
        max_ext = 1
        for s, per_part, lat in level_sel:
            for p in range(P):
                if len(per_part[p]):
                    e = lat[p].max(axis=0) - lat[p].min(axis=0) + 1
                    max_ext = max(max_ext, int(e.max()))
        bs_eff = min(bs_knob, max_ext)
    levels: List[LevelGrid] = []
    for s, per_part, lat in level_sel:
        # choose this level's block dims: a single dense bbox block when
        # that is no larger than the bs^3 tiling would be, else bs^3
        # tiles of only the occupied blocks (absolute bs-aligned ids, so
        # dims stay common across parts).  One key-sort per part serves
        # both the decision and the fill below.  Under merge, EVERY
        # level tiles at the shared bs_eff edge.
        bs_lvl = bs_eff if merge else bs_knob
        ext = np.zeros(3, dtype=np.int64)
        bmax = 1
        blocks = [None] * P      # (uniq_block_keys, binv) per part
        for p in range(P):
            if not len(per_part[p]):
                continue
            lo_p = lat[p].min(axis=0)
            ext = np.maximum(ext, lat[p].max(axis=0) + 1 - lo_p)
            bid = lat[p] // bs_lvl
            uniq, binv = np.unique(
                (bid[:, 0] << 42) + (bid[:, 1] << 21) + bid[:, 2],
                return_inverse=True)
            blocks[p] = (uniq, binv)
            bmax = max(bmax, len(uniq))
        if not ext.any():
            continue
        # the dense layout allocates prod(ext) of the COMMON (padded)
        # extents for every part — that, not any single part's bbox, is
        # what tiling competes against
        if not merge and int(np.prod(ext)) <= bmax * bs_knob ** 3:
            nb, (bx, by, bz) = 1, (int(ext[0]), int(ext[1]), int(ext[2]))
            tiled = False
        else:
            nb, (bx, by, bz) = bmax, (bs_lvl,) * 3
            tiled = True

        ck = np.zeros((P, nb, bx, by, bz))
        ce = np.zeros((P, nb, bx, by, bz))
        nn = (bx + 1) * (by + 1) * (bz + 1)
        nidx = np.full((P, nb, nn), pm.n_node_loc, dtype=np.int32)
        origin = np.zeros((P, nb, 3), dtype=np.int64)
        n_cells = np.zeros(P, dtype=np.int64)
        II, JJ, KK = np.meshgrid(np.arange(bx + 1), np.arange(by + 1),
                                 np.arange(bz + 1), indexing="ij")
        lat_nodes = np.stack([II, JJ, KK], axis=-1).reshape(-1, 3)  # (nn, 3)
        for p in range(P):
            e = per_part[p]
            n_cells[p] = len(e)
            if not len(e):
                continue
            if tiled:
                uniq, binv = blocks[p]
                blk_origin = np.stack([uniq >> 42, (uniq >> 21) & ((1 << 21) - 1),
                                       uniq & ((1 << 21) - 1)],
                                      axis=-1) * bs_lvl       # (B_p, 3)
                c = lat[p] - blk_origin[binv]
            else:
                blk_origin = lat[p].min(axis=0)[None]          # (1, 3)
                binv = np.zeros(len(e), dtype=np.int64)
                c = lat[p] - blk_origin[0]
            B_p = len(blk_origin)
            origin[p, :B_p] = blk_origin
            ck[p, binv, c[:, 0], c[:, 1], c[:, 2]] = model.ck[e]
            ce[p, binv, c[:, 0], c[:, 1], c[:, 2]] = model.ce[e]
            # node lattice -> local node ids (missing / non-local -> pad),
            # vectorized over this part's blocks
            g = (blk_origin[:, None, :] + lat_nodes[None]) * s   # (B_p, nn, 3)
            keys = (g[..., 0] + sy * g[..., 1] + sz * g[..., 2]).reshape(-1)
            kpos = np.searchsorted(node_keys, keys)
            kpos_c = np.minimum(kpos, len(node_keys) - 1)
            is_node = node_keys[kpos_c] == keys
            # global node id or -1 (key_order maps sorted pos -> node id)
            gnid = np.where(is_node, key_order[kpos_c], -1)
            loc_gids = pm.node_gid[p, : pm.nnode_p[p]]  # sorted
            lpos = np.searchsorted(loc_gids, np.where(gnid < 0, 0, gnid))
            lpos_c = np.minimum(lpos, len(loc_gids) - 1)
            is_loc = is_node & (loc_gids[lpos_c] == gnid)
            nidx[p, :B_p] = np.where(is_loc, lpos_c, pm.n_node_loc) \
                .astype(np.int32).reshape(B_p, nn)
        levels.append(LevelGrid(size=s, nb=nb, bx=bx, by=by, bz=bz,
                                origin=origin, ck=ck, ce=ce,
                                nidx=nidx, n_cells=n_cells))

    if merge and len(levels) > 1:
        # one block batch for the whole octree (size=0 marks the merged
        # multi-size batch; per-cell sizes live on in nidx/ck).  Slot
        # order after concatenation equals the level-order flattening
        # CombineMaps uses, so the maps below see identical numbering.
        cat = lambda attr: np.concatenate(
            [getattr(lv, attr) for lv in levels], axis=1)
        levels = [LevelGrid(
            size=0, nb=sum(lv.nb for lv in levels),
            bx=levels[0].bx, by=levels[0].by, bz=levels[0].bz,
            origin=cat("origin"), ck=cat("ck"), ce=cat("ce"),
            nidx=cat("nidx"),
            n_cells=np.sum([lv.n_cells for lv in levels], axis=0))]

    return HybridPartition(
        pm=pm,
        levels=levels,
        brick_Ke=np.asarray(lib["Ke"], np.float64),
        brick_diag=np.asarray(lib["diagKe"], np.float64),
        brick_Se=(np.asarray(lib["Se"], np.float64)
                  if lib.get("Se") is not None else None),
        # transition rows join the combine exactly when the ops build
        # them as node rows (the node-ELL layout, Ops.use_node_ell)
        combine=build_combine_maps(
            levels, pm.n_node_loc, P,
            [tb.node for tb in pm.type_blocks] if pm.ell is not None
            else []),
    )


def partition_env_knobs() -> Dict[str, object]:
    """Every env knob ``partition_hybrid`` consumes at PARTITION time,
    resolved by the module that owns the defaults.  Cache keys
    (solver/driver.py ``_partition_cached``) must consume THIS dict, not
    copy the defaults: a default change here must re-key cached
    partitions, never silently serve the old layout."""
    return {
        "block": int(os.environ.get("PCG_TPU_HYBRID_BLOCK", "8")),
        "merge": os.environ.get("PCG_TPU_HYBRID_MERGE", "0") == "1",
        "kd": combine_kd(),
    }


def combine_kd() -> Optional[int]:
    """Dense width of the gather-combine (slots gathered for EVERY node
    before falling to the heavy-node residual): PCG_TPU_HYBRID_KD, or
    None when unset — then ``build_combine_maps`` picks the width that
    gathers the fewest rows for the partition at hand."""
    raw = os.environ.get("PCG_TPU_HYBRID_KD", "")
    if not raw:
        return None
    kd = int(raw)
    if kd < 1:
        raise ValueError(f"PCG_TPU_HYBRID_KD must be >= 1, got {kd}")
    return kd


def _live_slots(ck: np.ndarray) -> np.ndarray:
    """(P, nb, bx, by, bz) cell scales -> (P, nb, lattice slots) bool:
    the lattice points that are a corner of at least one ck != 0 cell of
    their block.  Every other slot's stencil, diagonal, node-block and
    averaging output is an exact zero."""
    nz = ck != 0
    P, nb, bx, by, bz = nz.shape
    live = np.zeros((P, nb, bx + 1, by + 1, bz + 1), dtype=bool)
    for dx, dy, dz in _CORNERS:
        live[:, :, dx:dx + bx, dy:dy + by, dz:dz + bz] |= nz
    return live.reshape(P, nb, -1)


def _pick_kd(lens: List[np.ndarray], N: int) -> int:
    """The dense width that gathers the fewest rows per combine,
    ``KD*N + KE*H + N`` (``KD*N`` when no node is heavy), over the
    parts' per-node slot counts ``lens``."""
    lmax = max(int(ln.max(initial=0)) for ln in lens)

    def rows(kd):
        H = max(int((ln > kd).sum()) for ln in lens)
        return kd * N + ((lmax - kd) * H + N if H else 0)

    return min(range(1, max(lmax, 1) + 1), key=rows)


def build_combine_maps(levels: List[LevelGrid], n_node_loc: int, P: int,
                       trans_nodes: List[np.ndarray]
                       ) -> Optional[CombineMaps]:
    """Sort every live lattice slot and every transition row by target
    node and compose direct per-node source indices (see CombineMaps).
    ``trans_nodes``: the transition type blocks' (P, nn, N) local node
    ids in block order, or empty when their rows stay on the node-ELL
    path.  All host-side numpy, one argsort per part."""
    if not levels:
        return None
    Ns = sum(a[0].size for a in [lv.nidx for lv in levels] + trans_nodes)
    live = [_live_slots(lv.ck) for lv in levels]
    sorted_l, lens_l = [], []
    n_dropped = 0
    for p in range(P):
        lvl = np.concatenate([lv.nidx[p].reshape(-1) for lv in levels])
        keep = np.concatenate([m[p].reshape(-1) for m in live])
        n_dropped += int(((lvl < n_node_loc) & ~keep).sum())
        tgt = np.concatenate(
            [np.where(keep, lvl, n_node_loc)]
            + [tn[p].reshape(-1) for tn in trans_nodes]).astype(np.int64)
        slots = np.flatnonzero(tgt < n_node_loc)
        s_s = slots[np.argsort(tgt[slots], kind="stable")]
        lens = np.bincount(tgt[slots], minlength=n_node_loc)
        sorted_l.append((s_s, np.cumsum(lens) - lens))
        lens_l.append(lens)
    KD = combine_kd() or _pick_kd(lens_l, n_node_loc)
    KE = max(max(int(ln.max(initial=0)) for ln in lens_l) - KD, 0)
    H = max(int((ln > KD).sum()) for ln in lens_l)
    gidx = np.full((P, KD, n_node_loc), Ns, dtype=np.int32)
    hgidx = np.full((P, KE, H), Ns, dtype=np.int32)
    hpos = np.full((P, n_node_loc), H, dtype=np.int32)
    for p, ((s_s, starts), lens) in enumerate(zip(sorted_l, lens_l)):
        for k in range(KD):
            sel = lens > k
            gidx[p, k, sel] = s_s[starts[sel] + k]
        heavy = np.flatnonzero(lens > KD)
        hpos[p, heavy] = np.arange(len(heavy))
        for k in range(KE):
            sel = lens[heavy] > KD + k
            hgidx[p, k, np.flatnonzero(sel)] = \
                s_s[starts[heavy[sel]] + KD + k]
    return CombineMaps(n_slots=Ns, gidx=gidx, hgidx=hgidx, hpos=hpos,
                       n_dropped=n_dropped)


def device_data_hybrid(hp: HybridPartition, dtype=jnp.float64) -> dict:
    """``device_data`` plus the level grids and the combine maps, as host
    arrays (see ``device_data``)."""
    d = device_data(hp.pm, dtype)
    d["levels"] = [{
        "ck": np.asarray(lv.ck, dtype),
        "ce": np.asarray(lv.ce, dtype),
        "nidx": np.asarray(lv.nidx, np.int32),
    } for lv in hp.levels]
    d["brick_Ke"] = np.asarray(hp.brick_Ke, dtype)
    d["brick_diag"] = np.asarray(hp.brick_diag, dtype)
    if hp.brick_Se is not None:
        d["brick_Se"] = np.asarray(hp.brick_Se, dtype)
    if hp.combine is not None:
        d["combine"] = {"gidx": hp.combine.gidx, "hgidx": hp.combine.hgidx,
                        "hpos": hp.combine.hpos}
    return d


# corner offsets in the brick type's node order (== models/element.py
# HEX_CORNERS == _slot_layout(0)'s corner order, asserted in tests)
_CORNERS = np.array([[0, 0, 0], [1, 0, 0], [1, 1, 0], [0, 1, 0],
                     [0, 0, 1], [1, 0, 1], [1, 1, 1], [0, 1, 1]],
                    dtype=np.int64)


@dataclasses.dataclass(frozen=True)
class HybridOps(Ops):
    """General Ops over the transition blocks + dense level-grid stencils
    for the brick cells of each refinement level."""

    # static (nb, bx, by, bz) per level — shapes must be trace-constants
    level_dims: tuple = ()

    @classmethod
    def from_hybrid(cls, hp: HybridPartition, dot_dtype=jnp.float64,
                    axis_name=None,
                    precision=jax.lax.Precision.HIGHEST):
        pm = hp.pm
        return cls(n_loc=pm.n_loc, n_iface=pm.n_iface,
                   n_node_loc=pm.n_node_loc, n_node_iface=pm.n_node_iface,
                   dot_dtype=dot_dtype, axis_name=axis_name,
                   precision=precision,
                   use_node_ell=pm.ell is not None,
                   level_dims=tuple((lv.nb, lv.bx, lv.by, lv.bz)
                                    for lv in hp.levels))

    # -- level-grid primitives -----------------------------------------
    def _rows_pad(self, x):
        """x (P, n_loc) -> zero-padded node rows (P*(n_node_loc+1), 3)."""
        Pn = x.shape[0]
        x3 = x.reshape(Pn, self.n_node_loc, 3)
        return jnp.concatenate(
            [x3, jnp.zeros((Pn, 1, 3), x3.dtype)], axis=1
        ).reshape(Pn * (self.n_node_loc + 1), 3)

    def _level_gather(self, x3p, lv, dims, Pn):
        """Node-lattice gather: (P*nb, 3, bx+1, by+1, bz+1) block batch."""
        nb, bx, by, bz = dims
        nr = self.n_node_loc + 1
        offs = (jnp.arange(Pn, dtype=jnp.int32) * nr)[:, None, None]
        g = jnp.take(x3p, (lv["nidx"] + offs).reshape(-1), axis=0,
                     mode="clip")
        g = g.reshape(Pn * nb, bx + 1, by + 1, bz + 1, 3)
        return g.transpose(0, 4, 1, 2, 3)

    def _trans_in_combine(self, data) -> bool:
        """Whether the transition blocks' rows are slots of the combine
        maps (partition_hybrid builds them in exactly for the node-ELL
        layout), so the node-ELL sum is off the combine path.  A
        partition with no level grids has no maps."""
        return ("combine" in data and bool(data["levels"])
                and self.use_node_ell)

    @staticmethod
    def _take_rows(src, idx):
        """src (P, R, w), idx (P, ...) -> (P, ..., w): one flat row
        gather with the part folded into the row index, as Ops' gather
        primitives do."""
        Pn, R, w = src.shape
        offs = (jnp.arange(Pn, dtype=jnp.int32) * R).reshape(
            (Pn,) + (1,) * (idx.ndim - 1))
        g = jnp.take(src.reshape(Pn * R, w), (idx + offs).reshape(-1),
                     axis=0, mode="clip")
        return g.reshape(idx.shape + (w,))

    def _combined_gather_add(self, y, rows_levels, data, Pn,
                             rows_trans=None):
        """Scatter-free combine: add every level's lattice-slot rows and,
        when they are in the maps, the transition rows into y (P, n_loc)
        through the partition-composed slot->node maps (CombineMaps;
        rationale in the CombineMaps docstring).  ``rows_levels``:
        per-level (P, n_slots_l, w) arrays in level order; w is the row
        width (3 for matvec/diag).  ``rows_trans``: the transition
        blocks' (P, nn*N, w) rows in block order, or None for a consumer
        that has already added the transitions itself (zero rows)."""
        cm = data["combine"]
        w = rows_levels[0].shape[-1]
        dt = rows_levels[0].dtype
        parts = list(rows_levels)
        if self._trans_in_combine(data):
            parts += rows_trans if rows_trans is not None else [
                jnp.zeros((Pn, b["node"].shape[1] * b["node"].shape[2], w),
                          dt) for b in data["blocks"]]
        rows = jnp.concatenate(parts + [jnp.zeros((Pn, 1, w), dt)],
                               axis=1)                      # pad row
        acc = None
        for k in range(cm["gidx"].shape[1]):
            t = self._take_rows(rows, cm["gidx"][:, k])
            acc = t if acc is None else acc + t
        if cm["hgidx"].shape[2]:
            hacc = None
            for k in range(cm["hgidx"].shape[1]):
                t = self._take_rows(rows, cm["hgidx"][:, k])
                hacc = t if hacc is None else hacc + t
            # each heavy node's rest comes back by a gather: a zero row
            # after the heavy sums is every other node's hpos
            hacc = jnp.concatenate([hacc, jnp.zeros((Pn, 1, w), dt)],
                                   axis=1)
            acc = acc + self._take_rows(hacc, cm["hpos"])
        return (y.reshape(Pn, self.n_node_loc, w) + acc).reshape(Pn, -1)

    @staticmethod
    def _grid_rows(grid, Pn):
        """(P*nb, w, bx+1, by+1, bz+1) block-batch grid -> (P, slots, w)
        rows in the CombineMaps slot order."""
        w = grid.shape[1]
        return grid.transpose(0, 2, 3, 4, 1).reshape(Pn, -1, w)

    def _stencil(self, Ke, ck, xg):
        """Structured brick matvec on one level grid, as in
        parallel/structured.py: slice gather -> einsum -> sum of padded
        translates."""
        bx, by, bz = ck.shape[1], ck.shape[2], ck.shape[3]
        with jax.named_scope("mv/gather"):
            slots = [xg[:, :, dx:dx + bx, dy:dy + by, dz:dz + bz]
                     for dx, dy, dz in _CORNERS]
            u = jnp.concatenate(slots, axis=1)         # (P, 24, cells)
        with jax.named_scope("mv/contract"):
            v = jnp.einsum("de,pexyz->pdxyz", Ke, ck[:, None] * u,
                           precision=self.precision)
        with jax.named_scope("mv/scatter"):
            terms = []
            for a, (dx, dy, dz) in enumerate(_CORNERS):
                terms.append(jnp.pad(
                    v[:, 3 * a:3 * a + 3],
                    ((0, 0), (0, 0), (dx, 1 - dx), (dy, 1 - dy),
                     (dz, 1 - dz))))
            y = terms[0]
            for t in terms[1:]:
                y = y + t
        return y

    # -- operator protocol ---------------------------------------------
    def matvec_local(self, data, x):
        """Transition blocks, then each level grid, in the same
        ``mv/gather`` (node rows to element and level lattices),
        ``mv/contract`` (the element and brick stencil products) and
        ``mv/scatter`` (pad-add corner terms, the node combine, the
        springs) stages.  The transition rows (node-ELL layout) and the
        live lattice slots reach their nodes through ONE composed
        combine; a partition with no level grids sums its transitions
        through Ops.matvec_local's node-ELL path."""
        if x.ndim == 3:
            # RHS-block axis (Ops.matvec contract): the level-grid
            # gather/stencil/scatter machinery runs on flat vectors, so
            # the block batches with vmap (the inherited iface_assemble
            # handles the 3-D case natively — still ONE psum).
            return jax.vmap(lambda xc: self.matvec_local(data, xc),
                            in_axes=-1, out_axes=-1)(x)
        Pn = x.shape[0]
        rows_trans = (self._element_rows(data, x)
                      if self._trans_in_combine(data) else None)
        if data["blocks"] and rows_trans is None:
            y = Ops.matvec_local(self, data, x)
        else:
            with jax.named_scope("mv/scatter"):
                y = self._apply_springs(data, x, jnp.zeros_like(x))
        if data["levels"]:
            with jax.named_scope("mv/gather"):
                x3p = self._rows_pad(x)
            rows_levels = []
            for lv, dims in zip(data["levels"], self.level_dims):
                with jax.named_scope("mv/gather"):
                    xg = self._level_gather(x3p, lv, dims, Pn)
                    ck = lv["ck"].reshape((Pn * dims[0],)
                                          + lv["ck"].shape[2:])
                yg = self._stencil(data["brick_Ke"], ck, xg)
                with jax.named_scope("mv/scatter"):
                    rows_levels.append(self._grid_rows(yg, Pn))
            with jax.named_scope("mv/scatter"):
                y = self._combined_gather_add(y, rows_levels, data, Pn,
                                              rows_trans)
        return y

    def diag_local(self, data):
        Pn = data["weight"].shape[0]
        if data["blocks"]:
            y = Ops.diag_local(self, data)
        else:
            y = self._apply_springs_diag(
                data, jnp.zeros((Pn, self.n_loc), data["weight"].dtype))
        rows_levels = []
        for lv, dims in zip(data["levels"], self.level_dims):
            ck = lv["ck"].reshape((Pn * dims[0],) + lv["ck"].shape[2:])
            dk = data["brick_diag"]
            terms = []
            for a, (dx, dy, dz) in enumerate(_CORNERS):
                contrib = dk[3 * a:3 * a + 3][None, :, None, None, None] \
                    * ck[:, None]
                terms.append(jnp.pad(
                    contrib,
                    ((0, 0), (0, 0), (dx, 1 - dx), (dy, 1 - dy),
                     (dz, 1 - dz))))
            g = terms[0]
            for t in terms[1:]:
                g = g + t
            rows_levels.append(self._grid_rows(g, Pn))
        if rows_levels:
            y = self._combined_gather_add(y, rows_levels, data, Pn)
        return y

    def _node_block_local(self, data):
        """Transition-block node blocks (general path) + brick-cell corner
        blocks pad-translated onto each level's node grid."""
        if data["blocks"]:
            y = Ops._node_block_local(self, data)
        else:
            Pl = data["weight"].shape[0]
            y = self._springs_into_blocks(
                data, jnp.zeros((Pl, self.n_node_loc, 9),
                                data["weight"].dtype))
        from pcg_mpi_solver_tpu.ops.precond import corner_block_field

        rows_levels = []
        for lv, dims in zip(data["levels"], self.level_dims):
            Pn = lv["ck"].shape[0]
            ck = lv["ck"].reshape((Pn * dims[0],) + lv["ck"].shape[2:])
            g = corner_block_field(data["brick_Ke"], ck, _CORNERS)
            rows_levels.append(self._grid_rows(g, Pn))
        if rows_levels:
            Pn = y.shape[0]
            y = self._combined_gather_add(
                y.reshape(Pn, -1), rows_levels, data, Pn
            ).reshape(Pn, self.n_node_loc, 9)
        return y

    # -- export protocol (strain + nodal averaging over blocks + levels) --
    def elem_strain(self, data, x):
        out = Ops.elem_strain(self, data, x) if data["blocks"] else []
        Pn = x.shape[0]
        if data["levels"]:
            if "brick_Se" not in data:
                raise ValueError("strain export unavailable: the brick "
                                 "element library has no Se strain mode")
            x3p = self._rows_pad(x)
            for lv, dims in zip(data["levels"], self.level_dims):
                xg = self._level_gather(x3p, lv, dims, Pn)
                nb, bx, by, bz = dims
                slots = [xg[:, :, dx:dx + bx, dy:dy + by, dz:dz + bz]
                         for dx, dy, dz in _CORNERS]
                u = jnp.concatenate(slots, axis=1)
                ce = lv["ce"].reshape((Pn * nb,) + lv["ce"].shape[2:])
                eps = jnp.einsum("sd,pdxyz->psxyz", data["brick_Se"],
                                 ce[:, None] * u,
                                 precision=self.precision)
                # (P*nb, 6, cells) -> (P, 6, nb*cells): per-part cell
                # order stays aligned with elem_scale/nodal_average
                eps = eps.reshape(Pn, nb, 6, -1).transpose(0, 2, 1, 3)
                out.append(eps.reshape(Pn, 6, -1))
        return out

    def elem_scale(self, data):
        out = Ops.elem_scale(self, data) if data["blocks"] else []
        for lv in data["levels"]:
            Pn = lv["ck"].shape[0]
            out.append((lv["ck"] * lv["ce"]).reshape(Pn, -1))
        return out

    def nodal_average(self, data, vals_list):
        """Blocks + levels -> averaged nodal field.  vals_list aligns with
        elem_strain/elem_scale output order: blocks first, then levels."""
        nb = len(data["blocks"])
        k = vals_list[0].shape[1]
        Pl = vals_list[0].shape[0]
        dt = vals_list[0].dtype
        sums = jnp.zeros((Pl, k, self.n_node_loc), dt)
        counts = jnp.zeros((Pl, 1, self.n_node_loc), dt)

        def scat(s, ids, c):
            return s.at[:, ids].add(c, mode="drop")

        for blk, vals in zip(data["blocks"], vals_list[:nb]):
            node = blk["node"]
            nn = node.shape[1]
            ids = node.reshape(Pl, -1)
            contrib = jnp.broadcast_to(
                vals[:, :, None, :], (Pl, k, nn, vals.shape[2])
            ).reshape(Pl, k, -1)
            ones = jnp.ones((Pl, 1, nn * vals.shape[2]), dt)
            sums = jax.vmap(scat)(sums, ids, contrib)
            counts = jax.vmap(scat)(counts, ids, ones)

        rows_levels = []
        for lv, dims, vals in zip(data["levels"], self.level_dims,
                                  vals_list[nb:]):
            lnb, bx, by, bz = dims
            vg = vals.reshape(Pl, k, lnb, bx, by, bz) \
                .transpose(0, 2, 1, 3, 4, 5).reshape(Pl * lnb, k, bx, by, bz)
            # valid-cell mask: holes (ck == 0) must not count
            valid = (lv["ck"].reshape(Pl * lnb, bx, by, bz) != 0) \
                .astype(dt)[:, None]
            both = jnp.concatenate([vg * valid, valid], axis=1)
            terms = []
            for dx, dy, dz in _CORNERS:
                terms.append(jnp.pad(
                    both, ((0, 0), (0, 0), (dx, 1 - dx), (dy, 1 - dy),
                           (dz, 1 - dz))))
            g = terms[0]
            for t in terms[1:]:
                g = g + t                       # (P*nb, k+1, node grid)
            rows_levels.append(self._grid_rows(g, Pl))
        if rows_levels:
            # (sums, counts) as (P, n_node_loc, k+1) rows through the combine
            joined = jnp.concatenate([sums, counts], axis=1).transpose(0, 2, 1)
            joined = self._combined_gather_add(
                joined.reshape(Pl, -1), rows_levels, data, Pl
            ).reshape(Pl, self.n_node_loc, k + 1).transpose(0, 2, 1)
            sums, counts = joined[:, :k], joined[:, k:]

        both = jnp.concatenate([sums, counts], axis=1)
        both = self.niface_assemble(data, both)
        return both[:, :k] / (both[:, k:] + 1e-15)
