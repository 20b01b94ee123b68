"""Pallas fused structured matvec vs the XLA gather/einsum/scatter path.

Run in interpret mode (tests execute on the CPU backend, conftest.py); the
same kernel lowers to Mosaic on real TPU."""

import jax.numpy as jnp
import numpy as np
import pytest

from pcg_mpi_solver_tpu.models import make_cube_model
from pcg_mpi_solver_tpu.ops.pallas_matvec import structured_matvec_pallas
from pcg_mpi_solver_tpu.parallel.structured import (
    StructuredOps, device_data_structured, flat_cells, partition_structured)


@pytest.mark.parametrize("dims", [(6, 5, 4), (4, 4, 4), (7, 3, 5)])
def test_pallas_matvec_matches_xla(dims):
    nx, ny, nz = dims
    model = make_cube_model(nx, ny, nz, heterogeneous=True, seed=11)
    sp = partition_structured(model, 1)
    data = device_data_structured(sp, jnp.float32)
    ops = StructuredOps.from_partition(sp, dot_dtype=jnp.float32)

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(1, sp.n_loc)), jnp.float32)
    y_ref = np.asarray(ops.matvec_local(data, x))[0]

    blk = data["blocks"][0]
    xg = x.reshape(1, 3, nx + 1, ny + 1, nz + 1)[0]
    ck = ops._ck_grid(blk["ck"])[0]
    y = structured_matvec_pallas(xg, ck, blk["Ke"],
                                 interpret=True)
    np.testing.assert_allclose(
        np.asarray(y).reshape(-1), y_ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dims", [(6, 5, 4), (4, 4, 4), (7, 3, 5)])
def test_pallas_matvec_v2_matches_xla(dims):
    from pcg_mpi_solver_tpu.ops.pallas_matvec import structured_matvec_pallas_v2

    nx, ny, nz = dims
    model = make_cube_model(nx, ny, nz, heterogeneous=True, seed=11)
    sp = partition_structured(model, 1)
    data = device_data_structured(sp, jnp.float32)
    ops = StructuredOps.from_partition(sp, dot_dtype=jnp.float32)

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(1, sp.n_loc)), jnp.float32)
    y_ref = np.asarray(ops.matvec_local(data, x))[0]

    blk = data["blocks"][0]
    xg = x.reshape(1, 3, nx + 1, ny + 1, nz + 1)[0]
    ck = ops._ck_grid(blk["ck"])[0]
    y = structured_matvec_pallas_v2(xg, ck, blk["Ke"],
                                    interpret=True)
    np.testing.assert_allclose(
        np.asarray(y).reshape(-1), y_ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("variant", ["v3", "v4", "v5", "v6", "v7", "v8", "v9"])
@pytest.mark.parametrize("dims,planes", [((6, 5, 4), 2), ((4, 4, 4), 4),
                                         ((7, 3, 5), 3), ((5, 4, 3), 8)])
def test_pallas_matvec_chunked_matches_xla(variant, dims, planes):
    """Chunked variants vs the XLA matvec, incl. chunk sizes that do not
    divide nx+1 (tail handled by zero padding / skipped copies):
    v3 double-buffered MXU, v4 reshape-free, v5 layout-legal (canonical
    per-corner dots, aligned pad + lane roll), v6 slab-aligned DMA."""
    from pcg_mpi_solver_tpu.ops import pallas_matvec as pm

    fn = {"v3": pm.structured_matvec_pallas_v3,
          "v4": pm.structured_matvec_pallas_v4,
          "v5": pm.structured_matvec_pallas_v5,
          "v6": pm.structured_matvec_pallas_v6,
          "v7": pm.structured_matvec_pallas_v7,
          "v8": pm.structured_matvec_pallas_v8,
          "v9": pm.structured_matvec_pallas_v9}[variant]
    nx, ny, nz = dims
    model = make_cube_model(nx, ny, nz, heterogeneous=True, seed=11)
    sp = partition_structured(model, 1)
    data = device_data_structured(sp, jnp.float32)
    ops = StructuredOps.from_partition(sp, dot_dtype=jnp.float32)

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(1, sp.n_loc)), jnp.float32)
    y_ref = np.asarray(ops.matvec_local(data, x))[0]

    blk = data["blocks"][0]
    xg = x.reshape(1, 3, nx + 1, ny + 1, nz + 1)[0]
    ck = ops._ck_grid(blk["ck"])[0]
    y = fn(xg, ck, blk["Ke"], interpret=True, planes=planes)
    np.testing.assert_allclose(
        np.asarray(y).reshape(-1), y_ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kernel_fn", ["v1", "v2", "v3", "v4", "v5", "v6", "v7", "v8", "v9"])
def test_pallas_matvec_zero_ck_column_isolated(kernel_fn):
    """Cells with ck=0 must contribute nothing (the padded-cell trick the
    sharded integration — and v2's own gather padding — relies on)."""
    from pcg_mpi_solver_tpu.ops.pallas_matvec import (
        structured_matvec_pallas_v2, structured_matvec_pallas_v3,
        structured_matvec_pallas_v4, structured_matvec_pallas_v5,
        structured_matvec_pallas_v6, structured_matvec_pallas_v7,
        structured_matvec_pallas_v8, structured_matvec_pallas_v9)

    fn = {"v1": structured_matvec_pallas,
          "v2": structured_matvec_pallas_v2,
          "v3": structured_matvec_pallas_v3,
          "v4": structured_matvec_pallas_v4,
          "v5": structured_matvec_pallas_v5,
          "v6": structured_matvec_pallas_v6,
          "v7": structured_matvec_pallas_v7,
          "v8": structured_matvec_pallas_v8,
          "v9": structured_matvec_pallas_v9}[kernel_fn]
    model = make_cube_model(4, 3, 3, heterogeneous=True, seed=1)
    sp = partition_structured(model, 1)
    data = device_data_structured(sp, jnp.float32)
    blk = data["blocks"][0]
    ck0 = jnp.asarray(sp.ck[0], jnp.float32)
    ck_masked = ck0.at[:, :, -1].set(0.0)

    rng = np.random.default_rng(9)
    xg = jnp.asarray(rng.normal(size=(3, 5, 4, 4)), jnp.float32)
    y = fn(xg, ck_masked, blk["Ke"], interpret=True)
    # nodes on the far-z face only touch the zeroed cells via dz=1 corners;
    # recompute with the XLA path and compare
    ops = StructuredOps.from_partition(sp, dot_dtype=jnp.float32)
    ck_flat = flat_cells(np.asarray(ck_masked)[None])
    data2 = {"blocks": [{**blk, "ck": ck_flat}],
             **{k: v for k, v in data.items() if k != "blocks"}}
    y_ref = np.asarray(ops.matvec_local(
        data2, xg.reshape(1, -1)))[0]
    np.testing.assert_allclose(np.asarray(y).reshape(-1), y_ref,
                               rtol=2e-5, atol=2e-5)


def test_solver_pallas_interpret_structured_matches_xla():
    """SolverConfig.pallas='interpret' drives the REAL solver->kernel
    dispatch (grid reshape, leading-parts batching, f32 inner path)
    through the Pallas interpreter — the integration CI cannot get from
    kernel-level tests.  Must match the XLA path's iterations/solution."""
    from pcg_mpi_solver_tpu import RunConfig, SolverConfig
    from pcg_mpi_solver_tpu.parallel.mesh import make_mesh
    from pcg_mpi_solver_tpu.solver import Solver

    model = make_cube_model(8, 5, 4, heterogeneous=True, seed=6,
                            load="traction", load_value=1e6)
    res = {}
    for mode in ("off", "interpret"):
        cfg = RunConfig(solver=SolverConfig(
            tol=1e-6, max_iter=2000, dtype="float32",
            precision_mode="mixed", pallas=mode))
        s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1,
                   backend="structured")
        assert getattr(s.ops, "use_pallas", False) == (mode == "interpret")
        r = s.step(1.0)
        assert r.flag == 0, (mode, r)
        res[mode] = (int(r.iters), s.displacement_global())
    it_x, u_x = res["off"]
    it_p, u_p = res["interpret"]
    assert abs(it_x - it_p) <= 2, (it_x, it_p)
    # two f32 solves to tol=1e-6: agreement is bounded by the solver
    # tolerance times the solution scale, not by machine eps per element
    np.testing.assert_allclose(u_p, u_x, rtol=1e-3,
                               atol=1e-5 * float(np.abs(u_x).max()))


def test_solver_pallas_interpret_hybrid_matches_xla():
    """Same integration contract on the hybrid backend: the level-grid
    stencils route through batched_structured_matvec for every eligible
    level when pallas='interpret' (mirrors hybrid_pallas_enabled)."""
    from pcg_mpi_solver_tpu import RunConfig, SolverConfig
    from pcg_mpi_solver_tpu.models.octree import make_octree_model
    from pcg_mpi_solver_tpu.parallel.mesh import make_mesh
    from pcg_mpi_solver_tpu.solver import Solver

    model = make_octree_model(3, 3, 3, max_level=2, n_incl=2, seed=5,
                              load="traction", load_value=1e6)
    res = {}
    for mode in ("off", "interpret"):
        cfg = RunConfig(solver=SolverConfig(
            tol=1e-6, max_iter=3000, dtype="float32",
            precision_mode="mixed", pallas=mode))
        s = Solver(model, cfg, mesh=make_mesh(1), n_parts=1,
                   backend="hybrid")
        assert getattr(s.ops, "use_pallas", False) == (mode == "interpret")
        if mode == "interpret":
            assert any(s.ops.pallas_levels), s.ops.pallas_levels
        r = s.step(1.0)
        assert r.flag == 0, (mode, r)
        res[mode] = (int(r.iters), s.displacement_global())
    it_x, u_x = res["off"]
    it_p, u_p = res["interpret"]
    assert abs(it_x - it_p) <= 2, (it_x, it_p)
    np.testing.assert_allclose(u_p, u_x, rtol=1e-3,
                               atol=1e-5 * float(np.abs(u_x).max()))


# ----------------------------------------------------------------------
# pallas="auto"/"on" resolution: no fallback that hides the device
# ----------------------------------------------------------------------

def _fake_mesh(platform, kind):
    from types import SimpleNamespace

    dev = SimpleNamespace(platform=platform, device_kind=kind)
    return SimpleNamespace(devices=np.array([dev], dtype=object))


V5E = ("v6", "TPU v5 lite")


def test_pallas_auto_on_tpu_reraises_compile_failure(monkeypatch):
    """A kernel failure under "auto" on a verified device kind RAISES:
    no warning, no switch to the XLA matvec."""
    from pcg_mpi_solver_tpu.ops import pallas_matvec
    from pcg_mpi_solver_tpu.solver import driver

    def refuse(shapes, dtype=None):
        raise RuntimeError("Mosaic: unsupported shape")

    monkeypatch.setattr(pallas_matvec, "probe_shapes", refuse)
    monkeypatch.setattr(driver, "_PALLAS_PROBED", set())
    monkeypatch.setattr(driver, "PALLAS_VERIFIED", frozenset({V5E}))
    shapes = (((3, 9, 8, 7), (8, 7, 6)),)
    with pytest.raises(RuntimeError, match="Mosaic"):
        driver._pallas_enabled("auto", _fake_mesh("tpu", "TPU v5 lite"),
                               shapes=shapes)


def test_pallas_auto_decides_from_device_before_compiling(monkeypatch):
    """"auto" engages from platform + (variant, device_kind), probes the
    kernel once per shape set, and never probes off a verified pair."""
    from pcg_mpi_solver_tpu.ops import pallas_matvec
    from pcg_mpi_solver_tpu.solver import driver

    probed = []
    monkeypatch.setattr(pallas_matvec, "probe_shapes",
                        lambda shapes, dtype=None: probed.append(shapes))
    monkeypatch.setattr(driver, "_PALLAS_PROBED", set())
    monkeypatch.setattr(driver, "PALLAS_VERIFIED", frozenset())
    shapes = (((3, 9, 8, 7), (8, 7, 6)),)
    for plat, kind in (("cpu", "cpu"), ("tpu", "TPU v4"),
                       ("tpu", "TPU v5 lite")):
        assert not driver._pallas_enabled("auto", _fake_mesh(plat, kind),
                                          shapes=shapes)
    assert probed == []
    monkeypatch.setattr(driver, "PALLAS_VERIFIED", frozenset({V5E}))
    v5e = _fake_mesh("tpu", "TPU v5 lite")
    assert driver._pallas_enabled("auto", v5e, shapes=shapes)
    assert driver._pallas_enabled("auto", v5e, shapes=shapes)
    assert probed == [list(shapes)]
    # another variant is not vouched for by v6's verification
    monkeypatch.setenv("PCG_TPU_PALLAS_V", "9")
    assert not driver._pallas_enabled("auto", v5e, shapes=shapes)


def test_pallas_on_raises_on_unverified_tpu_kind(monkeypatch):
    """An explicit "on" runs no unverified kernel on a TPU: it raises
    before anything is lowered."""
    from pcg_mpi_solver_tpu.ops import pallas_matvec
    from pcg_mpi_solver_tpu.solver import driver

    probed = []
    monkeypatch.setattr(pallas_matvec, "probe_shapes",
                        lambda shapes, dtype=None: probed.append(shapes))
    monkeypatch.setattr(driver, "_PALLAS_PROBED", set())
    monkeypatch.setattr(driver, "PALLAS_VERIFIED", frozenset({V5E}))
    shapes = (((3, 9, 8, 7), (8, 7, 6)),)
    with pytest.raises(ValueError, match="not verified on 'TPU v4'"):
        driver._pallas_enabled("on", _fake_mesh("tpu", "TPU v4"),
                               shapes=shapes)
    assert probed == []
    assert driver._pallas_enabled("on", _fake_mesh("tpu", "TPU v5 lite"),
                                  shapes=shapes)
    assert probed == [list(shapes)]


def test_hybrid_pallas_on_raises_on_unverified_tpu_kind(monkeypatch):
    """The hybrid resolution goes through the same gate."""
    from types import SimpleNamespace

    from pcg_mpi_solver_tpu.parallel.hybrid import hybrid_pallas_enabled
    from pcg_mpi_solver_tpu.solver import driver

    monkeypatch.setattr(driver, "PALLAS_VERIFIED", frozenset())
    lv = SimpleNamespace(nb=2, bx=8, by=8, bz=8)
    hp = SimpleNamespace(pm=SimpleNamespace(n_parts=1), levels=[lv])
    mesh = _fake_mesh("tpu", "TPU v5 lite")
    with pytest.raises(ValueError, match="not verified"):
        hybrid_pallas_enabled(hp, "on", mesh)
    assert hybrid_pallas_enabled(hp, "auto", mesh) is False


def test_pallas_probe_failure_raises_on_every_process(monkeypatch):
    """Multi-process: the verdict is agreed, so a host whose probe
    passed raises too when another host's lowering failed."""
    import jax
    from jax.experimental import multihost_utils

    from pcg_mpi_solver_tpu.ops import pallas_matvec
    from pcg_mpi_solver_tpu.solver import driver

    monkeypatch.setattr(pallas_matvec, "probe_shapes",
                        lambda shapes, dtype=None: None)
    monkeypatch.setattr(driver, "_PALLAS_PROBED", set())
    monkeypatch.setattr(driver, "PALLAS_VERIFIED", frozenset({V5E}))
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    monkeypatch.setattr(multihost_utils, "process_allgather",
                        lambda x: np.asarray([[True], [False]]))
    with pytest.raises(RuntimeError, match="another process"):
        driver._pallas_enabled("auto", _fake_mesh("tpu", "TPU v5 lite"))


@pytest.mark.parametrize("mode", ["on", "interpret"])
def test_hybrid_explicit_pallas_that_cannot_engage_raises(mode):
    """An explicit kernel request on a partition whose every level is
    over the per-launch cap raises instead of warning."""
    from types import SimpleNamespace

    from pcg_mpi_solver_tpu.parallel.hybrid import (
        PALLAS_BATCH_CAP, hybrid_pallas_enabled)
    from pcg_mpi_solver_tpu.parallel.mesh import make_mesh

    lv = SimpleNamespace(nb=PALLAS_BATCH_CAP + 1, bx=8, by=8, bz=8)
    hp = SimpleNamespace(pm=SimpleNamespace(n_parts=1), levels=[lv])
    with pytest.raises(ValueError, match="cannot engage"):
        hybrid_pallas_enabled(hp, mode, make_mesh(1))
    assert hybrid_pallas_enabled(hp, "auto", make_mesh(1)) is False


def _kernel_dot_precisions(jaxpr):
    """Precision of every dot_general inside the pallas_call kernels of
    ``jaxpr`` (walks sub-jaxprs)."""
    import jax

    out = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "dot_general":
                out.append(eqn.params["precision"])
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        walk(sub)

    walk(jaxpr.jaxpr)
    return out


@pytest.mark.parametrize("variant", ["2", "3", "5", "6", "7", "8", "9"])
def test_pallas_kernel_dots_request_f32_precision(variant, monkeypatch):
    """Every MXU contraction in the kernels asks for full f32
    precision: Mosaic's default (one bf16 pass) put v6 2.5e-3 off the
    XLA matvec on the v5e and stalled the flagship solve — a fault the
    CPU interpreter cannot show, so the request itself is held here."""
    import jax
    import jax.numpy as jnp

    from pcg_mpi_solver_tpu.ops import pallas_matvec as pm

    monkeypatch.setenv("PCG_TPU_PALLAS_V", variant)
    fn = pm.selected_variant()[1]
    jaxpr = jax.make_jaxpr(lambda x, c, k: fn(x, c, k))(
        jax.ShapeDtypeStruct((3, 9, 8, 7), jnp.float32),
        jax.ShapeDtypeStruct((8, 7, 6), jnp.float32),
        jax.ShapeDtypeStruct((24, 24), jnp.float32))
    precs = _kernel_dot_precisions(jaxpr)
    assert precs, "no MXU contraction found in the kernel"
    hi = jax.lax.Precision.HIGHEST
    assert all(p is not None and all(q == hi for q in p) for p in precs), \
        precs
