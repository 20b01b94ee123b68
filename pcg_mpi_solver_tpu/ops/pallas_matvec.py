"""Pallas TPU kernel: fused structured-slab matvec (plane-march stencil).

The XLA formulation of the slab matvec (parallel/structured.py) materializes
the gathered corner array ``u`` and the per-cell product ``v`` — two
(24, n_cells) HBM round-trips (~650 MB each way at 10M dofs) plus an 8-step
read-modify-write scatter chain.  The operator itself is a 27-point
block-stencil; its arithmetic intensity is tiny, so HBM traffic is the whole
cost (reference hot loop: one dense matmul + bincount scatter per type,
pcg_solver.py:277-300 — same physics, same bound).

This kernel marches along the x axis one NODE PLANE at a time:

  step i reads  x[:, i:i+2]  (two (3, ny+1, nz+1) node planes, VMEM)
                ck[i]        (one (ny, nz) cell plane)
  computes the cell-plane product  v = Ke @ (ck * u)  as 24x24 unrolled
  VPU plane-FMAs (no (24, cells) array ever exists), and splits it into
  the corner-0 part (finishing output plane i) and the corner-1 part
  (carried in VMEM scratch to finish plane i+1 at the next step).

Every x plane is read exactly twice, ck once, y written once:
~140 MB total at 10M dofs vs ~1.7 GB for the unfused XLA path.

Layout note: planes are (ny+1, nz+1) 2-D VMEM blocks (sublane x lane), all
slice offsets are static (corner shifts in {0,1}), and the only dynamic
index is the leading-axis plane DMA — Mosaic-friendly by construction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from pcg_mpi_solver_tpu.models.element import HEX_CORNERS

#: precision of every f32 MXU contraction in these kernels.  Mosaic's
#: default is a single bf16 pass: on the v5e it put v6's product 2.5e-3
#: (relative) off the XLA form at every shape and stalled the flagship's
#: mixed solve at relres 1.0 (tools/pallas_parity.py, PR 21).
F32_DOT = jax.lax.Precision.HIGHEST

_CORNERS = HEX_CORNERS.astype(np.int64)  # (8, 3) offsets in {0,1}^3


def _matvec_kernel(ke_ref, x_hbm, ck_hbm, y_ref,
                   xv, ckv, carry, dma_sem, ck_sem, *, nx, ny, nz):
    """One grid step = one finished output node plane.

    ke_ref: (24, 24) VMEM (replicated element stiffness)
    x_hbm:  (3, nx+1, ny+1, nz+1) ANY/HBM input grid
    ck_hbm: (nx, ny, nz) ANY/HBM cell stiffness scales
    y_ref:  (3, 1, ny+1, nz+1) VMEM output block (plane i)
    xv:     (3, 2, ny+1, nz+1) VMEM scratch (planes i, i+1)
    ckv:    (1, ny, nz) VMEM scratch
    carry:  (3, ny+1, nz+1) VMEM scratch — corner-1 partial sums for plane i+1
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        carry[...] = jnp.zeros_like(carry)

    @pl.when(i < nx)
    def _work():
        cp_x = pltpu.make_async_copy(
            x_hbm.at[:, pl.ds(i, 2)], xv, dma_sem)
        cp_ck = pltpu.make_async_copy(
            ck_hbm.at[pl.ds(i, 1)], ckv, ck_sem)
        cp_x.start()
        cp_ck.start()
        cp_x.wait()
        cp_ck.wait()

        ck = ckv[0]                                    # (ny, nz)
        # t[e] = ck * gathered corner value, e = 3*corner + comp
        # (models/element.py dof ordering).
        t = [None] * 24
        for a, (dx, dy, dz) in enumerate(_CORNERS):
            for c in range(3):
                t[3 * a + c] = ck * xv[c, dx, dy:dy + ny, dz:dz + nz]
        # v[d] = sum_e Ke[d, e] * t[e]  — unrolled plane-FMAs on the VPU;
        # split by target corner as we go.  Corner placement is a zero-pad
        # (pure concatenate — Mosaic has no scatter-add lowering).
        lo = [jnp.zeros((ny + 1, nz + 1), xv.dtype) for _ in range(3)]
        hi = [jnp.zeros((ny + 1, nz + 1), xv.dtype) for _ in range(3)]
        for b, (ex, ey, ez) in enumerate(_CORNERS):
            for c in range(3):
                d = 3 * b + c
                v = ke_ref[d, 0] * t[0]
                for e in range(1, 24):
                    v = v + ke_ref[d, e] * t[e]
                tgt = lo if ex == 0 else hi
                tgt[c] = tgt[c] + jnp.pad(v, ((ey, 1 - ey), (ez, 1 - ez)))
        for c in range(3):
            y_ref[c, 0] = carry[c] + lo[c]
            carry[c] = hi[c]

    @pl.when(i == nx)
    def _last():
        for c in range(3):
            y_ref[c, 0] = carry[c]


def batched_structured_matvec(xg, ck, Ke, interpret=False):
    """Batched dispatch over the leading parts axis: one kernel launch per
    local part.  The structured backend always has exactly one local slab
    (n_parts == n_devices); the hybrid backend may carry several local
    parts and a few levels — the launches are sequential but share one
    compile cache entry, so the overhead is launch latency only (~us per
    part per level, negligible against a PCG iteration).

    PCG_TPU_PALLAS_V selects the variant (1 = per-plane VPU-FMA, 2 =
    per-plane MXU, 3 = chunked double-buffered MXU, 4 = reshape-free
    chunked — fails Mosaic concat-offset checks on its corner pads,
    5 = layout-legal chunked — fails Mosaic DMA slicing (size-1 sublane
    plane copies), default 6 = v5 compute + slab-aligned DMA,
    docs/RUNBOOK.md).  ``interpret`` runs the kernel through the Pallas
    interpreter (SolverConfig.pallas='interpret') so CI exercises this
    exact dispatch on CPU."""
    fn = selected_variant()[1]
    return jnp.stack([fn(xg[p], ck[p], Ke, interpret=interpret)
                      for p in range(xg.shape[0])])


def _planes_env(fn):
    """Wrap a chunked variant so it reads its chunk size from
    PCG_TPU_PALLAS_PLANES (default 8 — the smallest Mosaic-legal
    block), and trace it with x64 DISABLED: Pallas canonicalizes
    dynamic slice starts to the default int dtype, so under jax x64
    every dynamic memref_slice carries i64 indices — which Mosaic
    rejects — no matter what dtype the kernel passes (chipless x64
    check 2026-07-31).  The kernels are f32-only, so 32-bit tracing
    inside is always correct."""

    def wrapped(xg, ck, Ke, *, interpret=False):
        with jax.enable_x64(False):
            return fn(xg, ck, Ke, interpret=interpret,
                      planes=pallas_planes())

    return wrapped


def pallas_planes() -> int:
    """Resolved PCG_TPU_PALLAS_PLANES (cell planes per output block) —
    the ONE place the default lives.  Cache keys consume this function
    (solver/driver.py AOT step key) rather than copying the default, so
    a default change here re-keys cached step programs instead of
    silently serving a program built with the old block shape."""
    import os

    planes = int(os.environ.get("PCG_TPU_PALLAS_PLANES", "8"))
    if planes % 8 != 0:
        # a typo'd knob would otherwise fail Mosaic lowering and
        # silently degrade pallas='auto' to the XLA path
        raise ValueError(
            f"PCG_TPU_PALLAS_PLANES must be a multiple of 8, "
            f"got {planes}")
    return planes


def selected_variant():
    """(name, fn) of the kernel variant the PCG_TPU_PALLAS_V env knob
    selects — the single source of truth for dispatch AND probing.  Read
    at trace time: toggling the knob after a solver compiled does not
    retrace (build a new Solver to switch).

    Default v6.  On the v5e its dots ran at Mosaic's default single
    bf16 pass (2.5e-3 off the XLA form, which stalled the flagship's
    mixed solve); with :data:`F32_DOT` its product matches XLA on the
    chip at small shapes, but its Mosaic compile at a 150x150 cell
    plane grew from ~50 s to over 14 min (chipless), so no device kind
    has it verified at the flagship (solver/driver.PALLAS_VERIFIED).
    v9 has never run on the chip."""
    import os

    v = os.environ.get("PCG_TPU_PALLAS_V", "6")
    if v == "1":
        return "v1", structured_matvec_pallas
    if v == "2":
        return "v2", structured_matvec_pallas_v2
    if v == "3":
        return "v3", _planes_env(structured_matvec_pallas_v3)
    if v == "4":
        return "v4", _planes_env(structured_matvec_pallas_v4)
    if v == "5":
        return "v5", _planes_env(structured_matvec_pallas_v5)
    if v == "7":
        return "v7", _planes_env(structured_matvec_pallas_v7)
    if v == "6":
        return "v6", _planes_env(structured_matvec_pallas_v6)
    if v == "8":
        return "v8", _planes_env(structured_matvec_pallas_v8)
    if v != "9":
        raise ValueError(
            f"PCG_TPU_PALLAS_V must be 1|2|3|4|5|6|7|8|9, got {v!r}")
    return "v9", _planes_env(structured_matvec_pallas_v9)


def probe_shapes(shapes, dtype=jnp.float32) -> None:
    """Lower the kernel for each (node-grid, cell-grid) shape pair; raises
    if any fails.  Used by the driver's pallas='auto' resolution so a
    shape-dependent Pallas lowering failure raises at init, in well under
    a second.  Mosaic's own compile is not repeated here (~70 s at a
    150x150 cell plane): it runs once, inside the solve program, and a
    failure there raises too.  Probes the SAME variant
    batched_structured_matvec dispatches to."""
    fn = selected_variant()[1]
    fn = fn if hasattr(fn, "lower") else jax.jit(fn)
    for xg_shape, ck_shape in shapes:
        fn.lower(
            jax.ShapeDtypeStruct(xg_shape, dtype),
            jax.ShapeDtypeStruct(ck_shape, dtype),
            jax.ShapeDtypeStruct((24, 24), dtype))


@functools.partial(jax.jit, static_argnames=("interpret",))
def structured_matvec_pallas(xg, ck, Ke, *, interpret=False):
    """y = scatter(Ke @ (ck * gather(x))) on one structured slab.

    xg: (3, nx+1, ny+1, nz+1) f32 node grid
    ck: (nx, ny, nz) f32 cell scales
    Ke: (24, 24) f32
    returns y with xg's shape.  Matches StructuredOps.matvec_local (f32).
    """
    _, nxn, nyn, nzn = xg.shape
    nx, ny, nz = nxn - 1, nyn - 1, nzn - 1
    kernel = functools.partial(_matvec_kernel, nx=nx, ny=ny, nz=nz)
    return pl.pallas_call(
        kernel,
        grid=(nx + 1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),     # Ke
            pl.BlockSpec(memory_space=pl.ANY),         # x (manual DMA)
            pl.BlockSpec(memory_space=pl.ANY),         # ck (manual DMA)
        ],
        out_specs=pl.BlockSpec((3, 1, nyn, nzn), lambda i: (0, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((3, nxn, nyn, nzn), xg.dtype),
        scratch_shapes=[
            pltpu.VMEM((3, 2, nyn, nzn), xg.dtype),
            pltpu.VMEM((1, ny, nz), ck.dtype),
            pltpu.VMEM((3, nyn, nzn), xg.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(Ke, xg, ck)


# ----------------------------------------------------------------------
# v2: flat-lane plane march with a REAL MXU matmul per plane.
#
# v1 computes Ke @ (ck*u) as 576 unrolled VPU plane-FMAs — memory-optimal
# but VPU-compute-bound.  v2 flattens each (ny+1, nz+1) plane into one lane
# axis: with the cell grid padded to NODE-plane strides and ck = 0 in the
# padding (a zero-stiffness cell contributes nothing), every corner gather
# is a contiguous lane slice at a static offset {0, 1, nz+1, nz+2}, the
# element product is one (24,24) @ (24, M) dot_general on the MXU per
# plane, and the scatter is eight shifted lane-slice adds.  Same HBM
# traffic as v1, MXU instead of VPU for the FLOPs.
# ----------------------------------------------------------------------


def _matvec_kernel_v2(ke_ref, x_hbm, ck_hbm, y_ref,
                      xv, ckv, carry, dma_sem, ck_sem, *, nx, m, sy):
    """One grid step = one finished output node plane (flat lanes).

    ke_ref: (24, 24) VMEM
    x_hbm:  (3, nx+1, m) ANY/HBM — node planes, flat (ny+1)*(nz+1) lanes
    ck_hbm: (nx, m) ANY/HBM — cell planes PADDED to node strides, ck=0 pad
    y_ref:  (3, 1, m) VMEM output block (plane i)
    xv:     (3, 2, m + sy + 2) VMEM (planes i, i+1; zero tail for the
            padded-cell gather overhang)
    ckv:    (1, m) VMEM
    carry:  (3, m + sy + 2) VMEM — upper-corner partials for plane i+1
    """
    i = pl.program_id(0)
    mp = m + sy + 2

    @pl.when(i == 0)
    def _init():
        carry[...] = jnp.zeros_like(carry)
        xv[...] = jnp.zeros_like(xv)       # zero gather-overhang tails

    @pl.when(i < nx)
    def _work():
        cp_x = pltpu.make_async_copy(
            x_hbm.at[:, pl.ds(i, 2)], xv.at[:, :, :m], dma_sem)
        cp_ck = pltpu.make_async_copy(
            ck_hbm.at[pl.ds(i, 1)], ckv, ck_sem)
        cp_x.start()
        cp_ck.start()
        cp_x.wait()
        cp_ck.wait()

        ck = ckv[0]                                     # (m,)
        rows = []
        for a, (dx, dy, dz) in enumerate(_CORNERS):
            off = dy * sy + dz
            for c in range(3):
                rows.append(ck * xv[c, dx, off:off + m])
        u = jnp.stack(rows)                             # (24, m)
        v = jax.lax.dot_general(
            ke_ref[...], u, (((1,), (0,)), ((), ())),
            precision=F32_DOT,
            preferred_element_type=jnp.float32)         # (24, m) on the MXU
        # corner placement as zero-padded adds (pads with static widths
        # are pure concatenates — Mosaic has no scatter-add lowering)
        lo = jnp.zeros((3, mp), u.dtype)
        hi = jnp.zeros((3, mp), u.dtype)
        for a, (dx, dy, dz) in enumerate(_CORNERS):
            off = dy * sy + dz
            pad = jnp.pad(v[3 * a:3 * a + 3], ((0, 0), (off, mp - off - m)))
            if dx == 0:
                lo = lo + pad
            else:
                hi = hi + pad
        for c in range(3):
            y_ref[c, 0] = (carry[c] + lo[c])[:m]
            carry[c] = hi[c]

    @pl.when(i == nx)
    def _last():
        for c in range(3):
            y_ref[c, 0] = carry[c][:m]


@functools.partial(jax.jit, static_argnames=("interpret",))
def structured_matvec_pallas_v2(xg, ck, Ke, *, interpret=False):
    """Flat-lane MXU variant of :func:`structured_matvec_pallas`.

    Same signature/semantics: xg (3, nx+1, ny+1, nz+1), ck (nx, ny, nz),
    Ke (24, 24), all f32."""
    _, nxn, nyn, nzn = xg.shape
    nx, ny, nz = nxn - 1, nyn - 1, nzn - 1
    m = nyn * nzn
    x_flat = xg.reshape(3, nxn, m)
    ck_pad = jnp.pad(ck, ((0, 0), (0, 1), (0, 1))).reshape(nx, m)
    kernel = functools.partial(_matvec_kernel_v2, nx=nx, m=m, sy=nzn)
    y = pl.pallas_call(
        kernel,
        grid=(nx + 1,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),     # Ke
            pl.BlockSpec(memory_space=pl.ANY),         # x (manual DMA)
            pl.BlockSpec(memory_space=pl.ANY),         # ck (manual DMA)
        ],
        out_specs=pl.BlockSpec((3, 1, m), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((3, nxn, m), xg.dtype),
        scratch_shapes=[
            pltpu.VMEM((3, 2, m + nzn + 2), xg.dtype),
            pltpu.VMEM((1, m), ck.dtype),
            pltpu.VMEM((3, m + nzn + 2), xg.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )(Ke, x_flat, ck_pad)
    return y.reshape(3, nxn, nyn, nzn)


# ----------------------------------------------------------------------
# v3: C-plane chunks + double-buffered DMA.
#
# v2 marches one plane per grid step: ~microseconds of work per step, so
# fixed per-step costs (DMA issue/wait latency, loop overhead) dominate.
# v3 processes C cell planes per step.  The flat-lane trick extends to the
# x axis: within a chunk buffer of C+1 node planes, corner (dx,dy,dz) is
# the contiguous lane offset dx*M + dy*(nz+1) + dz, so the whole chunk is
# gathered by 24 slices and multiplied by 8 (24,3)@(3,C*M) MXU dots
# accumulated in VMEM (no (24, C*M) u buffer).  DMA for chunk j+1 is
# issued before chunk j's compute (double buffering).
# ----------------------------------------------------------------------


def _matvec_kernel_v3(ke_ref, x_hbm, ck_hbm, y_ref,
                      xv, ckv, acc, sems, ck_sems, *, g, cpp, nxn, m, sy):
    """One grid step = C finished output node planes (flat lanes).

    ke_ref: (24, 24) VMEM
    x_hbm:  (3, nxn, m) ANY/HBM — NOT padded; tail-chunk plane copies
            beyond nxn are skipped and the stale slot lanes they leave
            behind only ever multiply ck = 0 (ck IS zero-padded)
    ck_hbm: (g*cpp, m) ANY/HBM (zero-padded)
    y_ref:  (3, cpp, m) VMEM output block (planes j*cpp ..< (j+1)*cpp)
    xv:     (2, 3, (cpp+1)*m + sy + 2) VMEM — double-buffered chunk +
            one overlap plane + gather-overhang tail (zeroed once)
    ckv:    (2, cpp, m) VMEM
    acc:    (3, (cpp+1)*m + sy + 2) VMEM — chunk output accumulator;
            its tail plane [cpp*m:] is the carry into the next chunk
    """
    j = pl.program_id(0)
    cm = cpp * m

    def for_chunk(slot, chunk, act):
        """Start or wait the chunk's copies: cpp+1 node planes into flat
        lane offsets of the slot buffer + the ck plane block.  Descriptors
        are recreated identically at wait time (standard double-buffering
        pattern); out-of-range tail planes are skipped on BOTH sides."""
        for k in range(cpp + 1):
            # i32 ALWAYS: the static _init path (chunk = python 0)
            # otherwise traces plane as i64 under jax x64, and
            # Mosaic rejects i64 memref_slice indices (observed
            # on-HW 2026-07-31 from the driver's f64-mode probe)
            plane = jnp.asarray(chunk * cpp + k, jnp.int32)

            @pl.when(plane < nxn)
            def _cp():
                getattr(pltpu.make_async_copy(
                    x_hbm.at[:, plane],
                    xv.at[slot, :, pl.ds(jnp.asarray(k * m, jnp.int32), m)],
                    sems.at[slot]), act)()
        getattr(pltpu.make_async_copy(
            ck_hbm.at[pl.ds(chunk * cpp, cpp)],
            ckv.at[slot], ck_sems.at[slot]), act)()

    @pl.when(j == 0)
    def _init():
        xv[...] = jnp.zeros_like(xv)       # zero overhang tails once
        acc[...] = jnp.zeros_like(acc)
        for_chunk(0, 0, "start")

    # wait for this chunk's data; prefetch the next chunk
    slot = jax.lax.rem(j, jnp.asarray(2, j.dtype))
    for_chunk(slot, j, "wait")

    @pl.when(j + 1 < g)
    def _prefetch():
        for_chunk(1 - slot, j + 1, "start")

    xb = xv[slot]                                       # (3, (cpp+1)m + tail)
    ck = ckv[slot].reshape(1, cm)                       # (1, cm)
    # v = sum_a Ke[:, 3a:3a+3] @ (ck * x_slice_a)  — 8 MXU dots, no
    # (24, cm) gather buffer.  All slice offsets are STATIC (Mosaic has no
    # dynamic_slice lowering; the only dynamic index is the slot read).
    v = None
    for a, (dx, dy, dz) in enumerate(_CORNERS):
        off = dx * m + dy * sy + dz
        t = ck * xb[:, off:off + cm]                    # (3, cm)
        pa = jax.lax.dot_general(
            ke_ref[:, 3 * a:3 * a + 3], t, (((1,), (0,)), ((), ())),
            precision=F32_DOT, preferred_element_type=jnp.float32)
        v = pa if v is None else v + pa                 # (24, cm)
    # scatter: out[q + off_e] += v_e[q] as 8 zero-padded adds (Mosaic has
    # no scatter-add lowering; pads with static widths are pure
    # concatenates); the dx offset folds the carry to the next output
    # plane into the accumulator's overlap plane
    mp = (cpp + 1) * m + sy + 2
    out = acc[...]
    for a, (dx, dy, dz) in enumerate(_CORNERS):
        off = dx * m + dy * sy + dz
        out = out + jnp.pad(v[3 * a:3 * a + 3],
                            ((0, 0), (off, mp - off - cm)))
    y_ref[...] = out[:, :cm].reshape(3, cpp, m)
    # roll: overlap plane (+ tail zeros) becomes the next chunk's head
    acc[...] = jnp.pad(out[:, cm:cm + m + sy + 2],
                       ((0, 0), (0, mp - (m + sy + 2))))


@functools.partial(jax.jit, static_argnames=("interpret", "planes"))
def structured_matvec_pallas_v3(xg, ck, Ke, *, interpret=False, planes=8):
    """Chunked double-buffered variant of :func:`structured_matvec_pallas_v2`.

    Same signature/semantics; ``planes`` = cell planes per grid step.
    Default 8: the deployed Mosaic toolchain requires the last two dims
    of the output BlockSpec — (planes, m) here — to be (8, 128)-divisible
    or equal to the full array dims (docs/RUNBOOK.md "Mosaic lowering
    constraints"); m is the full lane axis, so planes must be a multiple
    of 8.  Override with PCG_TPU_PALLAS_PLANES (multiples of 8)."""
    _, nxn, nyn, nzn = xg.shape
    nx, ny, nz = nxn - 1, nyn - 1, nzn - 1
    m = nyn * nzn
    cpp = max(1, min(planes, nx + 1))
    g = -(-(nx + 1) // cpp)                 # ceil: covers all output planes
    x_flat = xg.reshape(3, nxn, m)          # free reshape, no copy
    # single pad; loop-invariant, so XLA hoists it out of the PCG loop
    ck_pad = jnp.pad(ck, ((0, g * cpp - nx), (0, 1), (0, 1))) \
        .reshape(g * cpp, m)
    kernel = functools.partial(_matvec_kernel_v3, g=g, cpp=cpp, nxn=nxn,
                               m=m, sy=nzn)
    y = pl.pallas_call(
        kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),     # Ke
            pl.BlockSpec(memory_space=pl.ANY),         # x (manual DMA)
            pl.BlockSpec(memory_space=pl.ANY),         # ck (manual DMA)
        ],
        out_specs=pl.BlockSpec((3, cpp, m), lambda j: (0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((3, g * cpp, m), xg.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, 3, (cpp + 1) * m + nzn + 2), xg.dtype),
            pltpu.VMEM((2, cpp, m), ck.dtype),
            pltpu.VMEM((3, (cpp + 1) * m + nzn + 2), xg.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(Ke, x_flat, ck_pad)
    return y[:, :nxn].reshape(3, nxn, nyn, nzn)


# ----------------------------------------------------------------------
# v4: v3's chunked double-buffered DMA, v2's per-plane compute — and NO
# lane-merging reshapes.
#
# The 2026-07-30 hardware session pinned v3's Mosaic failure to its
# (cpp, m) -> (cpp*m,) shape casts ("infer-vector-layout: unsupported
# shape cast" on tpu.reshape when m is not 128-divisible — m = nyn*nzn
# is 22801 at the flagship).  v4 keeps the plane axis as a real (sublane-
# tiled) array axis end to end: chunk buffers are (3, cpp+1, m+tail), a
# corner's dx offset selects a PLANE (static index) instead of a +dx*m
# lane offset, and each of the cpp planes in the chunk runs v2's flat-
# lane math ((24, m) stack -> one (24,24)@(24,m) MXU dot -> eight
# zero-padded lane adds).  Per-step cost stays chunk-sized (v3's fix for
# v2's per-plane grid overhead), the output BlockSpec is (3, cpp, m)
# with cpp % 8 == 0 and m the full lane axis — Mosaic-legal — and every
# slice offset is static.
# ----------------------------------------------------------------------


def _matvec_kernel_v4(ke_ref, x_hbm, ck_hbm, y_ref,
                      xv, ckv, acc, sems, ck_sems, *, g, cpp, nxn, m, sy):
    """One grid step = cpp finished output node planes.

    ke_ref: (24, 24) VMEM
    x_hbm:  (3, nxn, m) ANY/HBM — NOT padded; tail-chunk plane copies
            beyond nxn are skipped and the stale slot lanes they leave
            behind only ever multiply ck = 0 (ck IS zero-padded)
    ck_hbm: (g*cpp, m) ANY/HBM (zero-padded)
    y_ref:  (3, cpp, m) VMEM output block (planes j*cpp ..< (j+1)*cpp)
    xv:     (2, 3, cpp+1, m + sy + 2) VMEM — double-buffered node-plane
            chunk + one overlap plane; lane tail for the per-plane
            gather overhang (zeroed once, only ever multiplies ck = 0)
    ckv:    (2, cpp, m) VMEM
    acc:    (3, m + sy + 2) VMEM — carry: the chunk's last cell plane's
            upper-corner (dx=1) partials, finishing the NEXT chunk's
            first output plane
    """
    # i32 index arithmetic ALWAYS: under jax x64 (the solver's f64 dot
    # mode) program_id arithmetic otherwise promotes to i64, and Mosaic
    # rejects i64 memref_slice indices (observed on-HW 2026-07-30:
    # "tpu.memref_slice ... (i32, i64, i32)" VerificationError from the
    # driver's probe while the same kernel passed DMA under plain i32)
    j = jnp.asarray(pl.program_id(0), jnp.int32)
    mt = m + sy + 2

    def for_chunk(slot, chunk, act):
        """Start or wait the chunk's copies: cpp+1 node planes (each into
        the :m lanes of its own plane row) + the ck plane block.
        Descriptors are recreated identically at wait time (standard
        double-buffering pattern); out-of-range tail planes are skipped
        on BOTH sides."""
        for k in range(cpp + 1):
            # i32 ALWAYS: the static _init path (chunk = python 0)
            # otherwise traces plane as i64 under jax x64, and
            # Mosaic rejects i64 memref_slice indices (observed
            # on-HW 2026-07-31 from the driver's f64-mode probe)
            plane = jnp.asarray(chunk * cpp + k, jnp.int32)

            @pl.when(plane < nxn)
            def _cp():
                getattr(pltpu.make_async_copy(
                    x_hbm.at[:, plane],
                    xv.at[slot, :, jnp.asarray(k, jnp.int32),
                          pl.ds(jnp.asarray(0, jnp.int32), m)],
                    sems.at[slot]), act)()
        getattr(pltpu.make_async_copy(
            ck_hbm.at[pl.ds(chunk * cpp, cpp)],
            ckv.at[slot], ck_sems.at[slot]), act)()

    @pl.when(j == 0)
    def _init():
        xv[...] = jnp.zeros_like(xv)       # zero overhang tails once
        acc[...] = jnp.zeros_like(acc)
        for_chunk(0, 0, "start")

    # wait for this chunk's data; prefetch the next chunk
    slot = jax.lax.rem(j, jnp.asarray(2, j.dtype))
    for_chunk(slot, j, "wait")

    @pl.when(j + 1 < g)
    def _prefetch():
        for_chunk(1 - slot, j + 1, "start")

    xb = xv[slot]                                       # (3, cpp+1, mt)
    ckb = ckv[slot]                                     # (cpp, m)
    carry = acc[...]                                    # (3, mt)
    for k in range(cpp):
        ck = ckb[k]                                     # (m,)
        # u[e] = ck * corner value; dx picks the PLANE (static), dy/dz a
        # static lane offset — no flattened-x layout, hence no reshape
        rows = []
        for a, (dx, dy, dz) in enumerate(_CORNERS):
            off = dy * sy + dz
            for c in range(3):
                rows.append(ck * xb[c, k + dx, off:off + m])
        u = jnp.stack(rows)                             # (24, m)
        v = jax.lax.dot_general(
            ke_ref[...], u, (((1,), (0,)), ((), ())),
            precision=F32_DOT,
            preferred_element_type=jnp.float32)         # (24, m) on the MXU
        # corner placement as zero-padded lane adds (Mosaic has no
        # scatter-add lowering); dx routes to this output plane (lo) or
        # the next one (hi -> carry)
        lo = jnp.zeros((3, mt), u.dtype)
        hi = jnp.zeros((3, mt), u.dtype)
        for a, (dx, dy, dz) in enumerate(_CORNERS):
            off = dy * sy + dz
            pad = jnp.pad(v[3 * a:3 * a + 3], ((0, 0), (off, mt - off - m)))
            if dx == 0:
                lo = lo + pad
            else:
                hi = hi + pad
        out = carry + lo
        for c in range(3):
            y_ref[c, k] = out[c, :m]
        carry = hi
    acc[...] = carry


# ----------------------------------------------------------------------
# v5: v4 minus every Mosaic-illegal layout op.  The 2026-07-31 hardware
# session pinned v4's failure to its corner-placement pads:
#
#   tpu.concatenate (3x22801)+(3x153) -> (3x22954), in_layouts
#   {3,0} / {0,17} — "result/input offset mismatch on non-concat
#   dimension"
#
# i.e. (a) v[3a:3a+3] — a slice of a LOADED vector — carries sublane
# offset 3 while the pad's zeros are offset 0, and (b) the pad boundary
# m = 22801 = 17 (mod 128) puts the zeros at a misaligned lane offset.
# Three surgical fixes, same dataflow as v4 otherwise:
#
#   1. the per-corner product block is produced by its OWN small dot
#      ke[3a:3a+3] @ u — a fresh dot result gets a canonical {0,0}
#      layout, unlike v4's v[3a:3a+3] vector slice (sublane offset 3).
#      8 M=3 dots cost ~2.7x the one M=24 dot in MXU time, but the MXU
#      is ~0.3 us/plane against an HBM-bound kernel — irrelevant.
#   2. the lane axis is padded to m128 (a 128-multiple) on the host, so
#      the only remaining concatenate — the right-pad to mt128 — joins
#      at an aligned lane boundary with both inputs at {0,0}.
#   3. corner lane placement is pltpu.roll (tpu rotate primitive), not
#      an offset pad; the cyclic wrap only ever carries the zeroed lane
#      tail (mt128 - off >= m128 for every corner offset).
# ----------------------------------------------------------------------


def _matvec_kernel_v5(ke_ref, x_hbm, ck_hbm, y_ref,
                      xv, ckv, acc, sems, ck_sems,
                      *, g, cpp, nxn, m128, mt128, sy):
    """One grid step = cpp finished output node planes.

    ke_ref: (24, 24) VMEM
    x_hbm:  (3, nxn, m) ANY/HBM — NOT lane-padded (padding x would cost
            a full extra HBM round trip of the grid per matvec); VMEM
            rows are m128-wide, lanes [m:m128] stay zero from _init and
            only ever multiply ck = 0 (ck_hbm IS lane-padded — that pad
            is loop-invariant, so XLA hoists it out of the PCG loop)
    ck_hbm: (g*cpp, m128) ANY/HBM (zero-padded both axes)
    y_ref:  (3, cpp, m128) VMEM output block
    xv:     (2, 3, cpp+1, mt128) VMEM double-buffered chunk + overlap
            plane; zeroed lane tail holds the corner-read overhang
    ckv:    (2, cpp, m128) VMEM
    acc:    (3, mt128) VMEM — dx=1 partials carried to the next plane
    """
    j = jnp.asarray(pl.program_id(0), jnp.int32)  # i32 ALWAYS (see v4)
    m = x_hbm.shape[-1]

    def for_chunk(slot, chunk, act):
        for k in range(cpp + 1):
            # i32 ALWAYS: the static _init path (chunk = python 0)
            # otherwise traces plane as i64 under jax x64, and
            # Mosaic rejects i64 memref_slice indices (observed
            # on-HW 2026-07-31 from the driver's f64-mode probe)
            plane = jnp.asarray(chunk * cpp + k, jnp.int32)

            @pl.when(plane < nxn)
            def _cp():
                getattr(pltpu.make_async_copy(
                    x_hbm.at[:, plane],
                    xv.at[slot, :, jnp.asarray(k, jnp.int32),
                          pl.ds(jnp.asarray(0, jnp.int32), m)],
                    sems.at[slot]), act)()
        getattr(pltpu.make_async_copy(
            ck_hbm.at[pl.ds(chunk * cpp, cpp)],
            ckv.at[slot], ck_sems.at[slot]), act)()

    @pl.when(j == 0)
    def _init():
        xv[...] = jnp.zeros_like(xv)       # zero overhang tails once
        acc[...] = jnp.zeros_like(acc)
        for_chunk(0, 0, "start")

    slot = jax.lax.rem(j, jnp.asarray(2, j.dtype))
    for_chunk(slot, j, "wait")

    @pl.when(j + 1 < g)
    def _prefetch():
        for_chunk(1 - slot, j + 1, "start")

    ke = ke_ref[...]                                    # (24, 24)
    xb = xv[slot]                                       # (3, cpp+1, mt128)
    ckb = ckv[slot]                                     # (cpp, m128)
    carry = acc[...]                                    # (3, mt128)
    for k in range(cpp):
        ck = ckb[k]                                     # (m128,)
        rows = []
        for a, (dx, dy, dz) in enumerate(_CORNERS):
            off = dy * sy + dz
            for c in range(3):
                rows.append(ck * xb[c, k + dx, off:off + m128])
        u = jnp.stack(rows)                             # (24, m128)
        lo = jnp.zeros((3, mt128), u.dtype)
        hi = jnp.zeros((3, mt128), u.dtype)
        for b, (dx, dy, dz) in enumerate(_CORNERS):
            off = dy * sy + dz
            blk = jax.lax.dot_general(
                ke[3 * b:3 * b + 3], u, (((1,), (0,)), ((), ())),
                precision=F32_DOT,
                preferred_element_type=jnp.float32)     # (3, m128), {0,0}
            vp = jnp.pad(blk, ((0, 0), (0, mt128 - m128)))  # aligned concat
            if off:
                vp = pltpu.roll(vp, off, 1)             # lane rotate
            if dx == 0:
                lo = lo + vp
            else:
                hi = hi + vp
        out = carry + lo
        for c in range(3):
            y_ref[c, k] = out[c, :m128]
        carry = hi
    acc[...] = carry


@functools.partial(jax.jit, static_argnames=("interpret", "planes"))
def structured_matvec_pallas_v5(xg, ck, Ke, *, interpret=False, planes=8):
    """Layout-legal variant of :func:`structured_matvec_pallas_v4`.

    Same signature/semantics: xg (3, nx+1, ny+1, nz+1), ck (nx, ny, nz),
    Ke (24, 24), all f32; ``planes`` = cell planes per grid step
    (multiple of 8 — the output BlockSpec's sublane axis)."""
    _, nxn, nyn, nzn = xg.shape
    nx = nxn - 1
    m = nyn * nzn
    m128 = -(-m // 128) * 128
    sy = nzn
    mt128 = m128 + (-(-(sy + 2) // 128)) * 128
    cpp = max(1, min(planes, ((nx + 1 + 7) // 8) * 8))
    g = -(-(nx + 1) // cpp)                 # ceil: covers all output planes
    x_flat = xg.reshape(3, nxn, m)          # free reshape, no copy
    # ck pads are loop-invariant, so XLA hoists them out of the PCG loop
    ck_pad = jnp.pad(ck, ((0, g * cpp - nx), (0, 1), (0, 1))) \
        .reshape(g * cpp, m)
    ck_pad = jnp.pad(ck_pad, ((0, 0), (0, m128 - m)))
    kernel = functools.partial(_matvec_kernel_v5, g=g, cpp=cpp, nxn=nxn,
                               m128=m128, mt128=mt128, sy=sy)
    y = pl.pallas_call(
        kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),     # Ke
            pl.BlockSpec(memory_space=pl.ANY),         # x (manual DMA)
            pl.BlockSpec(memory_space=pl.ANY),         # ck (manual DMA)
        ],
        out_specs=pl.BlockSpec((3, cpp, m128), lambda j: (0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((3, g * cpp, m128), xg.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, 3, cpp + 1, mt128), xg.dtype),
            pltpu.VMEM((2, cpp, m128), ck.dtype),
            pltpu.VMEM((3, mt128), xg.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(Ke, x_flat, ck_pad)
    return y[:, :nxn, :m].reshape(3, nxn, nyn, nzn)


@functools.partial(jax.jit, static_argnames=("interpret", "planes"))
def structured_matvec_pallas_v4(xg, ck, Ke, *, interpret=False, planes=8):
    """Reshape-free chunked variant of :func:`structured_matvec_pallas_v3`.

    Same signature/semantics: xg (3, nx+1, ny+1, nz+1), ck (nx, ny, nz),
    Ke (24, 24), all f32; ``planes`` = cell planes per grid step
    (multiple of 8 — the output BlockSpec's sublane axis)."""
    _, nxn, nyn, nzn = xg.shape
    nx = nxn - 1
    m = nyn * nzn
    cpp = max(1, min(planes, ((nx + 1 + 7) // 8) * 8))
    g = -(-(nx + 1) // cpp)                 # ceil: covers all output planes
    x_flat = xg.reshape(3, nxn, m)          # free reshape, no copy
    # single pad; loop-invariant, so XLA hoists it out of the PCG loop
    ck_pad = jnp.pad(ck, ((0, g * cpp - nx), (0, 1), (0, 1))) \
        .reshape(g * cpp, m)
    kernel = functools.partial(_matvec_kernel_v4, g=g, cpp=cpp, nxn=nxn,
                               m=m, sy=nzn)
    y = pl.pallas_call(
        kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),     # Ke
            pl.BlockSpec(memory_space=pl.ANY),         # x (manual DMA)
            pl.BlockSpec(memory_space=pl.ANY),         # ck (manual DMA)
        ],
        out_specs=pl.BlockSpec((3, cpp, m), lambda j: (0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((3, g * cpp, m), xg.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, 3, cpp + 1, m + nzn + 2), xg.dtype),
            pltpu.VMEM((2, cpp, m), ck.dtype),
            pltpu.VMEM((3, m + nzn + 2), xg.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(Ke, x_flat, ck_pad)
    return y[:, :nxn].reshape(3, nxn, nyn, nzn)


# ----------------------------------------------------------------------
# v6: v5's compute, slab-aligned DMA.
#
# The 2026-07-31 wave-3 A/B showed v5 lowering PAST v4's concat error
# into the DMA legality check v1 hit from the start:
#
#   tpu.memref_slice (3,152,22912) -> (3,1,22801): "Slice shape along
#   dimension 1 must be aligned to tiling (8), but is 1"
#
# i.e. on this toolchain a DMA may slice a TILED dimension only in
# multiples of the tile (8 sublanes / 128 lanes) at tile-aligned
# offsets; the per-plane x copies (one node plane = a size-1 sublane
# slice) that every variant v1-v5 used are categorically illegal —
# v3/v4 just died in earlier layout passes before reaching this check.
# v6 keeps v5's compute body (fresh per-corner dots, m128-aligned pads,
# pltpu.roll placement — everything v4/v5 already fixed) and makes every
# DMA slab-aligned:
#
#   1. x is host-padded to (3, g*cpp + 8, m128) — lanes to a
#      128-multiple, planes so every slab read is in bounds.  The pad is
#      one extra HBM round-trip of x per matvec (~0.1 ms at the 10M-dof
#      flagship) — acceptable until the structured backend keeps x in
#      padded layout natively.
#   2. each grid step DMAs ONE slab of cpp+8 planes (cpp % 8 == 0, so
#      both the chunk offset j*cpp and the slice shape cpp+8 are
#      8-aligned) at FULL m128 lane width into rows [0, cpp+8) of the
#      mt128-wide chunk buffer (lane slice offset 0, shape m128 — a
#      128-multiple).  The 8 extra planes per chunk cover the +dx=1
#      corner overlap (only 1 is needed; 8 is the smallest legal slab),
#      costing 2x x reads at cpp=8 — ~84 MB/matvec at the flagship
#      against the unfused path's ~1.7 GB.
#   3. ck was already slab-copied (cpp planes, m128 lanes) — unchanged.
# ----------------------------------------------------------------------


def _matvec_kernel_v6(ke_ref, x_hbm, ck_hbm, y_ref,
                      xv, ckv, acc, sems, ck_sems,
                      *, g, cpp, m128, mt128, sy):
    """One grid step = cpp finished output node planes.

    ke_ref: (24, 24) VMEM
    x_hbm:  (3, g*cpp + 8, m128) ANY/HBM — lane- AND plane-padded on the
            host (see v6 header note); pad lanes/planes are zero, and
            out-of-range corner reads contribute nothing because the
            OUTPUT block is scaled by ck = 0 there
    ck_hbm: (g*cpp, m128) ANY/HBM (zero-padded both axes)
    y_ref:  (3, cpp, m128) VMEM output block
    xv:     (2, 3, cpp+8, mt128) VMEM double-buffered slab; lanes
            [m128, mt128) stay zero from _init and hold the corner-read
            overhang
    ckv:    (2, cpp, m128) VMEM
    acc:    (3, mt128) VMEM — dx=1 partials carried to the next plane
    """
    j = jnp.asarray(pl.program_id(0), jnp.int32)  # i32 ALWAYS (see v4)

    def for_chunk(slot, chunk, act):
        # NOTE on index dtypes: Pallas canonicalizes indices to the
        # DEFAULT int dtype, so under jax x64 every dynamic memref_slice
        # would carry i64 indices — which Mosaic rejects — regardless of
        # what dtype is passed here.  The fix is structural: _planes_env
        # traces every kernel under jax.enable_x64(False) (verified
        # sufficient by the chipless x64 checks, 2026-07-31).
        c0 = jnp.asarray(chunk * cpp, jnp.int32)
        z = jnp.asarray(0, jnp.int32)
        getattr(pltpu.make_async_copy(
            x_hbm.at[:, pl.ds(c0, cpp + 8), :],
            xv.at[slot, :, :, pl.ds(z, m128)], sems.at[slot]), act)()
        getattr(pltpu.make_async_copy(
            ck_hbm.at[pl.ds(c0, cpp)],
            ckv.at[slot], ck_sems.at[slot]), act)()

    @pl.when(j == 0)
    def _init():
        xv[...] = jnp.zeros_like(xv)       # zero overhang tails once
        acc[...] = jnp.zeros_like(acc)
        for_chunk(0, 0, "start")

    slot = jax.lax.rem(j, jnp.asarray(2, j.dtype))
    for_chunk(slot, j, "wait")

    @pl.when(j + 1 < g)
    def _prefetch():
        for_chunk(1 - slot, j + 1, "start")

    # ---- compute: v5's corner dots and roll placement, with ck HOISTED
    # OUT of the contraction: ck[l] is per CELL (lane l), identical for
    # all 24 gathered rows, so  sum_e Ke[d,e]*(ck*x_e) == ck*sum_e(...)
    # — the output block is scaled ONCE instead of 24 input rows.  The
    # 24 scaled input vectors were the kernel's Mosaic scoped-vmem hot
    # spot: the unrolled plane loop's live arena overflowed VMEM at any
    # m (chipless-compile bisection 2026-07-31); raw xb slices are views
    # and cost nothing.
    ke = ke_ref[...]                                    # (24, 24)
    xb = xv[slot]                                       # (3, cpp+8, mt128)
    ckb = ckv[slot]                                     # (cpp, m128)
    carry = acc[...]                                    # (3, mt128)
    for k in range(cpp):
        ck = ckb[k]                                     # (m128,)
        rows = []
        for a, (dx, dy, dz) in enumerate(_CORNERS):
            off = dy * sy + dz
            for c in range(3):
                rows.append(xb[c, k + dx, off:off + m128])
        u = jnp.stack(rows)                             # (24, m128)
        lo = jnp.zeros((3, mt128), u.dtype)
        hi = jnp.zeros((3, mt128), u.dtype)
        for b, (dx, dy, dz) in enumerate(_CORNERS):
            off = dy * sy + dz
            blk = jax.lax.dot_general(
                ke[3 * b:3 * b + 3], u, (((1,), (0,)), ((), ())),
                precision=F32_DOT,
                preferred_element_type=jnp.float32)     # (3, m128), {0,0}
            blk = ck * blk                              # hoisted ck scale
            vp = jnp.pad(blk, ((0, 0), (0, mt128 - m128)))  # aligned concat
            if off:
                vp = pltpu.roll(vp, off, 1)             # lane rotate
            if dx == 0:
                lo = lo + vp
            else:
                hi = hi + vp
        out = carry + lo
        for c in range(3):
            y_ref[c, k] = out[c, :m128]
        carry = hi
    acc[...] = carry


@functools.partial(jax.jit, static_argnames=("interpret", "planes"))
def structured_matvec_pallas_v6(xg, ck, Ke, *, interpret=False, planes=8):
    """Slab-DMA variant of :func:`structured_matvec_pallas_v5`.

    Same signature/semantics: xg (3, nx+1, ny+1, nz+1), ck (nx, ny, nz),
    Ke (24, 24), all f32; ``planes`` = cell planes per grid step
    (multiple of 8 — the output BlockSpec's sublane axis AND the DMA
    slab alignment).  VMEM budget caps planes at 8 for flagship m."""
    _, nxn, nyn, nzn = xg.shape
    nx = nxn - 1
    m = nyn * nzn
    m128 = -(-m // 128) * 128
    sy = nzn
    mt128 = m128 + (-(-(sy + 2) // 128)) * 128
    cpp = max(1, min(planes, ((nx + 1 + 7) // 8) * 8))
    g = -(-(nx + 1) // cpp)                 # ceil: covers all output planes
    x_flat = xg.reshape(3, nxn, m)          # free reshape, no copy
    # x pad: ONE fused pad to (planes, lanes) the slab DMA can read
    # whole; costs an extra HBM round trip of x per matvec (v6 header).
    x_pad = jnp.pad(x_flat, ((0, 0), (0, g * cpp + 8 - nxn), (0, m128 - m)))
    # ck pads are loop-invariant, so XLA hoists them out of the PCG loop
    ck_pad = jnp.pad(ck, ((0, g * cpp - nx), (0, 1), (0, 1))) \
        .reshape(g * cpp, m)
    ck_pad = jnp.pad(ck_pad, ((0, 0), (0, m128 - m)))
    kernel = functools.partial(_matvec_kernel_v6, g=g, cpp=cpp,
                               m128=m128, mt128=mt128, sy=sy)
    y = pl.pallas_call(
        kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),     # Ke
            pl.BlockSpec(memory_space=pl.ANY),         # x (manual DMA)
            pl.BlockSpec(memory_space=pl.ANY),         # ck (manual DMA)
        ],
        out_specs=pl.BlockSpec((3, cpp, m128), lambda j: (0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((3, g * cpp, m128), xg.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, 3, cpp + 8, mt128), xg.dtype),
            pltpu.VMEM((2, cpp, m128), ck.dtype),
            pltpu.VMEM((3, mt128), xg.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        # the unrolled plane loop's live arena exceeds the 16 MB default
        # scoped limit at >=128^3 (chipless bisection 2026-07-31); v5e
        # VMEM is far larger — raise the per-kernel cap
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(Ke, x_pad, ck_pad)
    return y[:, :nxn, :m].reshape(3, nxn, nyn, nzn)


# ----------------------------------------------------------------------
# v7: v6's slab DMA, roll-only compute.
#
# v6 still contains two op classes Mosaic has never been observed to
# lower in this kernel family (every variant so far died at its first
# unproven op, serially): (a) VALUE lane-slices at unaligned offsets —
# the u build reads xb[c, k+dx, off:off+m128] at off = dy*sy+dz, whose
# result carries a non-canonical lane-offset layout into an elementwise
# multiply and a 24-row stack (v4's concat rejection came from exactly
# such offset layouts); (b) the output pad-concat at the m128 boundary.
# v7 removes both: every lane placement — input gather AND output
# placement — is a pltpu.roll (tpu rotate, canonical {0,0} result) of a
# full mt128-wide row, and the zero tail of the ck mask kills the
# cyclic wrap:
#
#   input:  u_row = ck_mt * roll(x_row, mt128 - off)   # u[l] = ck*x[l+off]
#           (wrap lanes l >= mt128-off carry head values, but ck_mt is
#           zero for l >= m, and l+off never wraps for l < m)
#   dot:    ke[3b:3b+3] @ u  -> (3, mt128), {0,0}, no pad needed
#   output: roll(blk, +off) accumulated into mt128-wide lo/hi
#
# ck is host-padded to mt128 (not m128) so its DMA stays full-width and
# no in-kernel pad exists at all.
# ----------------------------------------------------------------------


def _matvec_kernel_v7(ke_ref, x_hbm, ck_hbm, y_ref,
                      xv, ckv, acc, sems, ck_sems,
                      *, g, cpp, m128, mt128, sy):
    """One grid step = cpp finished output node planes.

    ke_ref: (24, 24) VMEM
    x_hbm:  (3, g*cpp + 8, m128) ANY/HBM (lane- and plane-padded, zeros)
    ck_hbm: (g*cpp, mt128) ANY/HBM (zero-padded both axes, FULL mt width)
    y_ref:  (3, cpp, m128) VMEM output block
    xv:     (2, 3, cpp+8, mt128) VMEM double-buffered slab; lanes
            [m128, mt128) stay zero from _init
    ckv:    (2, cpp, mt128) VMEM
    acc:    (3, mt128) VMEM — dx=1 partials carried to the next plane
    """
    j = jnp.asarray(pl.program_id(0), jnp.int32)  # i32 ALWAYS (see v4)

    def for_chunk(slot, chunk, act):
        # i32 ALWAYS, including literal zeros (index promotion, see v6)
        c0 = jnp.asarray(chunk * cpp, jnp.int32)
        z = jnp.asarray(0, jnp.int32)
        getattr(pltpu.make_async_copy(
            x_hbm.at[:, pl.ds(c0, cpp + 8), :],
            xv.at[slot, :, :, pl.ds(z, m128)], sems.at[slot]), act)()
        getattr(pltpu.make_async_copy(
            ck_hbm.at[pl.ds(c0, cpp)],
            ckv.at[slot], ck_sems.at[slot]), act)()

    @pl.when(j == 0)
    def _init():
        xv[...] = jnp.zeros_like(xv)       # zero overhang tails once
        acc[...] = jnp.zeros_like(acc)
        for_chunk(0, 0, "start")

    slot = jax.lax.rem(j, jnp.asarray(2, j.dtype))
    for_chunk(slot, j, "wait")

    @pl.when(j + 1 < g)
    def _prefetch():
        for_chunk(1 - slot, j + 1, "start")

    ke = ke_ref[...]                                    # (24, 24)
    xb = xv[slot]                                       # (3, cpp+8, mt128)
    ckb = ckv[slot]                                     # (cpp, mt128)
    carry = acc[...]                                    # (3, mt128)
    for k in range(cpp):
        ck = ckb[k]                                     # (mt128,), 0 tail
        rows = []
        for a, (dx, dy, dz) in enumerate(_CORNERS):
            off = int(dy * sy + dz)
            for c in range(3):
                base = xb[c, k + dx]                    # (mt128,) full row
                if off:
                    base = pltpu.roll(base, mt128 - off, 0)
                rows.append(ck * base)
        u = jnp.stack(rows)                             # (24, mt128), {0,0}
        lo = jnp.zeros((3, mt128), u.dtype)
        hi = jnp.zeros((3, mt128), u.dtype)
        for b, (dx, dy, dz) in enumerate(_CORNERS):
            off = int(dy * sy + dz)
            blk = jax.lax.dot_general(
                ke[3 * b:3 * b + 3], u, (((1,), (0,)), ((), ())),
                precision=F32_DOT,
                preferred_element_type=jnp.float32)     # (3, mt128), {0,0}
            if off:
                blk = pltpu.roll(blk, off, 1)           # lane placement
            if dx == 0:
                lo = lo + blk
            else:
                hi = hi + blk
        out = carry + lo
        for c in range(3):
            y_ref[c, k] = out[c, :m128]
        carry = hi
    acc[...] = carry


@functools.partial(jax.jit, static_argnames=("interpret", "planes"))
def structured_matvec_pallas_v7(xg, ck, Ke, *, interpret=False, planes=8):
    """Roll-only variant of :func:`structured_matvec_pallas_v6`.

    Same signature/semantics: xg (3, nx+1, ny+1, nz+1), ck (nx, ny, nz),
    Ke (24, 24), all f32; ``planes`` = cell planes per grid step
    (multiple of 8)."""
    _, nxn, nyn, nzn = xg.shape
    nx = nxn - 1
    m = nyn * nzn
    m128 = -(-m // 128) * 128
    sy = nzn
    mt128 = m128 + (-(-(sy + 2) // 128)) * 128
    cpp = max(1, min(planes, ((nx + 1 + 7) // 8) * 8))
    g = -(-(nx + 1) // cpp)                 # ceil: covers all output planes
    x_flat = xg.reshape(3, nxn, m)          # free reshape, no copy
    x_pad = jnp.pad(x_flat, ((0, 0), (0, g * cpp + 8 - nxn), (0, m128 - m)))
    # ck pads are loop-invariant, so XLA hoists them out of the PCG loop;
    # FULL mt128 lane width so no pad op exists inside the kernel
    ck_pad = jnp.pad(ck, ((0, g * cpp - nx), (0, 1), (0, 1))) \
        .reshape(g * cpp, m)
    ck_pad = jnp.pad(ck_pad, ((0, 0), (0, mt128 - m)))
    kernel = functools.partial(_matvec_kernel_v7, g=g, cpp=cpp,
                               m128=m128, mt128=mt128, sy=sy)
    y = pl.pallas_call(
        kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),     # Ke
            pl.BlockSpec(memory_space=pl.ANY),         # x (manual DMA)
            pl.BlockSpec(memory_space=pl.ANY),         # ck (manual DMA)
        ],
        out_specs=pl.BlockSpec((3, cpp, m128), lambda j: (0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((3, g * cpp, m128), xg.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, 3, cpp + 8, mt128), xg.dtype),
            pltpu.VMEM((2, cpp, mt128), ck.dtype),
            pltpu.VMEM((3, mt128), xg.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(Ke, x_pad, ck_pad)
    return y[:, :nxn, :m].reshape(3, nxn, nyn, nzn)


# ----------------------------------------------------------------------
# v8: v6 with the plane loop GRID-IZED — grid (g, cpp), one cell plane
# per step.
#
# The chipless-compile bisection (2026-07-31, tools/aot_compile_check.py)
# pinned v6's RESOURCE_EXHAUSTED at >=128^3 to Mosaic's scoped-vmem
# arena: the python-unrolled cpp-plane loop keeps every iteration's
# temporaries live simultaneously (u alone is 24 x m128 x 4 B = 2.2 MB
# at the flagship m — eight live copies blow the ~16 MB budget together
# with the slab buffers).  Making the plane index a GRID dimension
# bounds the arena to ONE plane's temporaries; the output block is
# revisited across the cpp inner steps (index_map ignores the inner
# dim — Mosaic keeps the block resident until j changes), rows are
# written at the DYNAMIC sublane index kk and read at dynamic kk+dx —
# both verified to lower on the v5e toolchain by the chipless probes.
# Everything else (slab DMA, i32 indices, ck hoisted out of the
# contraction, roll placement) is v6's.
# ----------------------------------------------------------------------


def _matvec_kernel_v8(ke_ref, x_hbm, ck_hbm, y_ref,
                      xv, ckv, acc, sem, ck_sem,
                      *, g, cpp, m128, mt128, sy):
    """One grid step = ONE cell plane; cpp steps finish an output block.

    Shapes as _matvec_kernel_v6 except the slab is SINGLE-buffered
    ((3, cpp+8, mt128), no prefetch): the saved 4.4 MB keeps the scoped
    request inside VMEM at flagship m, and removing the dynamic ``slot``
    index leaves the row reads with ONE dynamic index (kk+dx) — Mosaic
    rejects dynamic loads with two ("dynamic load with unaligned
    indices", chipless probe 2026-07-31).  The lost copy/compute overlap
    is one slab DMA (~5 us at flagship) per cpp planes of compute.
    ``acc`` carries dx=1 partials from every plane to the next."""
    j = jnp.asarray(pl.program_id(0), jnp.int32)   # chunk
    kk = jnp.asarray(pl.program_id(1), jnp.int32)  # plane within chunk

    def for_chunk(chunk, act):
        # index dtypes: see _matvec_kernel_v6.for_chunk (the x64 story
        # is handled structurally by _planes_env's enable_x64(False))
        c0 = jnp.asarray(chunk * cpp, jnp.int32)
        z = jnp.asarray(0, jnp.int32)
        getattr(pltpu.make_async_copy(
            x_hbm.at[:, pl.ds(c0, cpp + 8), :],
            xv.at[:, :, pl.ds(z, m128)], sem), act)()
        getattr(pltpu.make_async_copy(
            ck_hbm.at[pl.ds(c0, cpp)],
            ckv, ck_sem), act)()

    @pl.when((j == 0) & (kk == 0))
    def _init():
        xv[...] = jnp.zeros_like(xv)       # zero overhang tails once
        acc[...] = jnp.zeros_like(acc)

    @pl.when(kk == 0)
    def _arrive():
        for_chunk(j, "start")
        for_chunk(j, "wait")

    ke = ke_ref[...]                                    # (24, 24)
    ck = ckv[kk]                                        # (m128,)
    rows = []
    for a, (dx, dy, dz) in enumerate(_CORNERS):
        off = dy * sy + dz
        for c in range(3):
            rows.append(xv[c, kk + dx, off:off + m128])
    u = jnp.stack(rows)                                 # (24, m128)
    lo = jnp.zeros((3, mt128), u.dtype)
    hi = jnp.zeros((3, mt128), u.dtype)
    for b, (dx, dy, dz) in enumerate(_CORNERS):
        off = dy * sy + dz
        blk = jax.lax.dot_general(
            ke[3 * b:3 * b + 3], u, (((1,), (0,)), ((), ())),
            precision=F32_DOT,
            preferred_element_type=jnp.float32)         # (3, m128), {0,0}
        blk = ck * blk                                  # hoisted ck scale
        vp = jnp.pad(blk, ((0, 0), (0, mt128 - m128)))  # aligned concat
        if off:
            vp = pltpu.roll(vp, off, 1)                 # lane rotate
        if dx == 0:
            lo = lo + vp
        else:
            hi = hi + vp
    out = acc[...] + lo
    for c in range(3):
        y_ref[c, kk] = out[c, :m128]
    acc[...] = hi


@functools.partial(jax.jit, static_argnames=("interpret", "planes"))
def structured_matvec_pallas_v8(xg, ck, Ke, *, interpret=False, planes=8):
    """Plane-per-grid-step variant of :func:`structured_matvec_pallas_v6`.

    Same signature/semantics: xg (3, nx+1, ny+1, nz+1), ck (nx, ny, nz),
    Ke (24, 24), all f32; ``planes`` = cell planes per output block
    (multiple of 8 — the output block's sublane axis)."""
    _, nxn, nyn, nzn = xg.shape
    nx = nxn - 1
    m = nyn * nzn
    m128 = -(-m // 128) * 128
    sy = nzn
    mt128 = m128 + (-(-(sy + 2) // 128)) * 128
    cpp = max(1, min(planes, ((nx + 1 + 7) // 8) * 8))
    g = -(-(nx + 1) // cpp)                 # ceil: covers all output planes
    x_flat = xg.reshape(3, nxn, m)          # free reshape, no copy
    x_pad = jnp.pad(x_flat, ((0, 0), (0, g * cpp + 8 - nxn), (0, m128 - m)))
    ck_pad = jnp.pad(ck, ((0, g * cpp - nx), (0, 1), (0, 1))) \
        .reshape(g * cpp, m)
    ck_pad = jnp.pad(ck_pad, ((0, 0), (0, m128 - m)))
    kernel = functools.partial(_matvec_kernel_v8, g=g, cpp=cpp,
                               m128=m128, mt128=mt128, sy=sy)
    y = pl.pallas_call(
        kernel,
        grid=(g, cpp),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),     # Ke
            pl.BlockSpec(memory_space=pl.ANY),         # x (manual DMA)
            pl.BlockSpec(memory_space=pl.ANY),         # ck (manual DMA)
        ],
        out_specs=pl.BlockSpec((3, cpp, m128), lambda j, k: (0, j, 0)),
        out_shape=jax.ShapeDtypeStruct((3, g * cpp, m128), xg.dtype),
        scratch_shapes=[
            pltpu.VMEM((3, cpp + 8, mt128), xg.dtype),
            pltpu.VMEM((cpp, m128), ck.dtype),
            pltpu.VMEM((3, mt128), xg.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
        # the scoped request at flagship m is 16.54 MB against the 16 MB
        # default limit (chipless compile 2026-07-31); v5e VMEM is far
        # larger — raise the per-kernel cap instead of shaving buffers
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(Ke, x_pad, ck_pad)
    return y[:, :nxn, :m].reshape(3, nxn, nyn, nzn)


# ----------------------------------------------------------------------
# v9: dot-built gather — NO concat/stack/pad of offset vectors anywhere.
#
# The first HARDWARE compiles of v6/v8 (2026-08-01, terminal
# tpu_compile_helper) rejected the u stack with a Mosaic error the
# build-host chipless pipeline accepts: "tpu.concatenate: result/input
# offset mismatch on non-concat dimension" — the 24 row slices
# xb[c, k+dx, off:off+m128] carry lane-offset layouts off%128 in
# {0, 1, 23, 24} and the DEPLOYED Mosaic has no relayout for
# lane-offset-mismatched concat inputs (the local toolchain does).
# The two toolchains differ; chipless-verified is necessary, not
# sufficient.
#
# v9 therefore never materializes a misaligned vector at all:
#
#   * the slab is PLANE-MAJOR — x_hbm (g*cpp+8, 3, m128) — so a corner
#     read is xb[k+dx]: a majormost-index memref slice yielding a
#     (3, mt128) block at canonical {0,0} layout (same op class as
#     xv[slot], lowered by every variant since v3);
#   * the dy/dz lane shift is a 2-D static pltpu.roll of that block
#     (the op every variant's OUTPUT path already lowers), applied to
#     the full mt128 width: xroll_a[c, l] = x[c, k+dx, l+off_a] for
#     all real l, and the cyclic wrap only touches lanes the ck mask
#     zeroes (ck is zero for pad cells, and real cells never read past
#     m — see the v7 header for the same argument);
#   * the (24, m) gathered array u is never BUILT: the product
#     v = Ke @ u is accumulated directly as eight MXU dots
#         v += keT[a] . xroll_a,   keT[a] = Ke[:, 3a:3a+3]  as (3, 24)
#     (contraction over the 3 components; every operand and result
#     lives at {0,0});
#   * output corner blocks are EXTRACTED BY DOT, not by row slicing:
#         blk_b = sel[b] . (ck * v),   sel[b] (3, 24) one-hot rows
#     so the placement roll and the lo/hi accumulation only ever see
#     {0,0} (3, mt128) blocks.  No jnp.pad exists in the kernel (ck is
#     host-padded to full mt128 width, as in v7).
#
# Cost vs v6: the 8 (3,24)@(24,m) output dots are replaced by
# 8 gather dots + 8 sel dots of the same MAC count — ~2x the (already
# tiny) FLOPs — plus 8 input rolls; the kernel stays DMA/HBM-bound by
# design.  The slab reads cpp+1 planes per chunk (v6 read cpp+8: its
# plane axis was tiled second-minor and DMA extents had to be 8-tile
# multiples; plane-major has no such constraint).  The host-side
# pad/transpose to plane-major costs one extra x round-trip per
# matvec, same class as v6's x_pad (header note 1).
# ----------------------------------------------------------------------


def _matvec_kernel_v9(ket_ref, sel_ref, x_hbm, ck_hbm, y_ref,
                      xv, ckv, acc, sems, ck_sems,
                      *, g, cpp, m128, mt128, sy):
    """One grid step = cpp finished output node planes.

    The component axis is physically FOUR everywhere (3 dof + one zero
    row): Mosaic tiles the second-minor axis at 4 and requires every
    memref-slice extent along it to be tile-aligned (chipless probe
    2026-08-01) — so x planes, ket/sel operands, the accumulators and
    the output block all carry the dead 4th row (zero in, zero out).

    ket_ref: (8, 4, 24) VMEM — ket[a,:3] = Ke[:, 3a:3a+3].T, row 3 zero
    sel_ref: (8, 4, 24) VMEM — sel[b, c, 3b+c] = 1 (c < 3), row 3 zero
    x_hbm:   (g*cpp + 1, 4, m128) ANY/HBM, plane-major, zero-padded
    ck_hbm:  (g*cpp, mt128) ANY/HBM (zero-padded, FULL mt width)
    y_ref:   (cpp, 4, m128) VMEM output block (plane-major)
    xv:      (2, cpp+1, 4, mt128) VMEM double-buffered slab (the plane
             axis is MAJORMOST, so the DMA extent cpp+1 needs no 8-tile
             alignment — v6's +8 overhang is gone); lanes [m128, mt128)
             stay zero from _init
    ckv:     (2, cpp, mt128) VMEM
    acc:     (4, mt128) VMEM — dx=1 partials carried to the next plane
    """
    j = jnp.asarray(pl.program_id(0), jnp.int32)  # i32 ALWAYS (see v4)

    def for_chunk(slot, chunk, act):
        # i32 ALWAYS, including literal zeros (index promotion, see v6)
        c0 = jnp.asarray(chunk * cpp, jnp.int32)
        z = jnp.asarray(0, jnp.int32)
        getattr(pltpu.make_async_copy(
            x_hbm.at[pl.ds(c0, cpp + 1)],
            xv.at[slot, :, :, pl.ds(z, m128)], sems.at[slot]), act)()
        getattr(pltpu.make_async_copy(
            ck_hbm.at[pl.ds(c0, cpp)],
            ckv.at[slot], ck_sems.at[slot]), act)()

    @pl.when(j == 0)
    def _init():
        xv[...] = jnp.zeros_like(xv)       # zero overhang tails once
        acc[...] = jnp.zeros_like(acc)
        for_chunk(0, 0, "start")

    slot = jax.lax.rem(j, jnp.asarray(2, j.dtype))
    for_chunk(slot, j, "wait")

    @pl.when(j + 1 < g)
    def _prefetch():
        for_chunk(1 - slot, j + 1, "start")

    xb = xv[slot]                                       # (cpp+1, 4, mt128)
    ckb = ckv[slot]                                     # (cpp, mt128)
    carry = acc[...]                                    # (4, mt128)
    for k in range(cpp):
        ck = ckb[k]                                     # (mt128,), 0 tail
        planes = (xb[k], xb[k + 1])                     # (4, mt128) {0,0}
        v = None
        for a, (dx, dy, dz) in enumerate(_CORNERS):
            off = int(dy * sy + dz)
            xr = planes[dx]
            if off:
                xr = pltpu.roll(xr, mt128 - off, 1)     # xr[l] = x[l+off]
            ket = ket_ref[a]                            # (4, 24) {0,0}
            d = jax.lax.dot_general(
                ket, xr, (((0,), (0,)), ((), ())),
                precision=F32_DOT,
                preferred_element_type=jnp.float32)     # (24, mt128) {0,0}
            v = d if v is None else v + d
        w = ck * v                                      # hoisted ck scale
        lo = jnp.zeros((4, mt128), w.dtype)
        hi = jnp.zeros((4, mt128), w.dtype)
        for b, (dx, dy, dz) in enumerate(_CORNERS):
            off = int(dy * sy + dz)
            sel = sel_ref[b]                            # (4, 24) one-hot
            blk = jax.lax.dot_general(
                sel, w, (((1,), (0,)), ((), ())),
                precision=F32_DOT,
                preferred_element_type=jnp.float32)     # (4, mt128) {0,0}
            if off:
                blk = pltpu.roll(blk, off, 1)           # lane placement
            if dx == 0:
                lo = lo + blk
            else:
                hi = hi + blk
        out = carry + lo
        y_ref[k] = out[:, :m128]
        carry = hi
    acc[...] = carry


@functools.partial(jax.jit, static_argnames=("interpret", "planes"))
def structured_matvec_pallas_v9(xg, ck, Ke, *, interpret=False, planes=8):
    """Dot-built-gather variant of :func:`structured_matvec_pallas_v6`.

    Same signature/semantics: xg (3, nx+1, ny+1, nz+1), ck (nx, ny, nz),
    Ke (24, 24), all f32; ``planes`` = cell planes per grid step
    (multiple of 8)."""
    _, nxn, nyn, nzn = xg.shape
    nx = nxn - 1
    m = nyn * nzn
    m128 = -(-m // 128) * 128
    sy = nzn
    mt128 = m128 + (-(-(sy + 2) // 128)) * 128
    cpp = max(1, min(planes, ((nx + 1 + 7) // 8) * 8))
    g = -(-(nx + 1) // cpp)                 # ceil: covers all output planes
    x_flat = xg.reshape(3, nxn, m)          # free reshape, no copy
    # plane-major with a zero 4th component row (tiling alignment, see
    # kernel docstring): a corner read inside the kernel is then a
    # majormost-index (4, mt128) block slice at {0,0}
    x_pad = jnp.pad(x_flat, ((0, 1), (0, g * cpp + 1 - nxn),
                             (0, m128 - m))).transpose(1, 0, 2)
    # ck pads are loop-invariant, so XLA hoists them out of the PCG
    # loop; FULL mt128 lane width so no pad op exists inside the kernel
    ck_pad = jnp.pad(ck, ((0, g * cpp - nx), (0, 1), (0, 1))) \
        .reshape(g * cpp, m)
    ck_pad = jnp.pad(ck_pad, ((0, 0), (0, mt128 - m)))
    ket = jnp.stack([
        jnp.concatenate([Ke[:, 3 * a:3 * a + 3].T,
                         jnp.zeros((1, 24), Ke.dtype)]) for a in range(8)])
    sel_np = np.zeros((8, 4, 24), np.float32)
    for b in range(8):
        for c in range(3):
            sel_np[b, c, 3 * b + c] = 1.0
    sel = jnp.asarray(sel_np)
    kernel = functools.partial(_matvec_kernel_v9, g=g, cpp=cpp,
                               m128=m128, mt128=mt128, sy=sy)
    y = pl.pallas_call(
        kernel,
        grid=(g,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),     # ket
            pl.BlockSpec(memory_space=pltpu.VMEM),     # sel
            pl.BlockSpec(memory_space=pl.ANY),         # x (manual DMA)
            pl.BlockSpec(memory_space=pl.ANY),         # ck (manual DMA)
        ],
        out_specs=pl.BlockSpec((cpp, 4, m128), lambda j: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g * cpp, 4, m128), xg.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, cpp + 1, 4, mt128), xg.dtype),
            pltpu.VMEM((2, cpp, mt128), ck.dtype),
            pltpu.VMEM((4, mt128), xg.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        # plane-major slab: each (4, mt128) plane occupies a 4-sublane
        # tile -> ~7 MB both slots at flagship m; raise the per-kernel
        # cap as for v6/v8
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(ket, sel, x_pad, ck_pad)
    return y[:nxn, :3, :m].transpose(1, 0, 2).reshape(3, nxn, nyn, nzn)
