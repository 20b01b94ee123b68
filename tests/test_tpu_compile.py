"""Chipless compiles of the main path's kernels for a described TPU v5e.

The installed libtpu compiles for a chip that is described, not
attached (``jax.experimental.topologies``): what Mosaic or XLA would
refuse on the chip is refused here, at no chip time.  The topology is
described only inside the module fixture — never at import, in a
``skipif``/``parametrize`` or in conftest.py — so that under xdist only
the worker given this file loads libtpu, and every worker collects the
same tests.

Shapes: the v6 kernel's compile time grows with its cell plane (ny*nz),
and more steeply since its dots request f32 precision (six MXU passes):
a 64x64 plane takes ~15 s, a 40x40 plane ~4 s, a 150x150 plane over
14 min (tools/pallas_parity.py --chipless).  The slab shapes here are
therefore those of a 40^3 cube, on one chip and per chip of four (the
largest near a few seconds); the hybrid level shapes are the
single-brick levels of the bench octree at n0=12 and n0=8 (chip_smoke.py's
phase (b)), level 4, and the 8^3 block; the XLA f32 matvec runs at the
150^3 flagship size.
"""

import os

import numpy as np
import pytest


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                              # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=np.float32):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_v6(cells, sharding, monkeypatch):
    """Compile the default Pallas kernel, as the solver dispatches it
    (under x64, as the mixed solve runs), at one (nx, ny, nz) cell grid;
    returns the compiled text."""
    import jax

    from pcg_mpi_solver_tpu.ops.pallas_matvec import selected_variant

    monkeypatch.delenv("PCG_TPU_PALLAS_V", raising=False)
    monkeypatch.delenv("PCG_TPU_PALLAS_PLANES", raising=False)
    name, kernel = selected_variant()
    assert name == "v6"
    nodes = (3,) + tuple(c + 1 for c in cells)
    compiled = jax.jit(lambda x, ck, ke: kernel(x, ck, ke)).lower(
        _sds(nodes, sharding), _sds(cells, sharding),
        _sds((24, 24), sharding)).compile()
    return compiled.as_text()


@pytest.mark.parametrize("cells", [(40, 40, 40), (10, 40, 40)],
                         ids=["cube40_one_chip", "cube40_slab_of_four"])
def test_pallas_v6_slab_compiles_for_v5e(one_chip, cells, monkeypatch):
    text = _compile_v6(cells, one_chip, monkeypatch)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("cells", [(22, 24, 24), (16, 16, 16), (8, 8, 8)],
                         ids=["level_22x24x24", "level_16", "block_8"])
def test_pallas_v6_hybrid_level_compiles_for_v5e(one_chip, cells,
                                                 monkeypatch):
    text = _compile_v6(cells, one_chip, monkeypatch)
    assert "tpu_custom_call" in text


def test_structured_f32_matvec_program_compiles_for_v5e(one_chip):
    """The XLA gse slab matvec in f32 at the 150^3 flagship, the form
    every structured f32 iteration runs when the kernel is off."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from pcg_mpi_solver_tpu.models import make_cube_model
    from pcg_mpi_solver_tpu.parallel.structured import (
        StructuredOps, partition_structured)

    n = 150
    sp = partition_structured(make_cube_model(4, 4, 4), 1)
    ops = dataclasses.replace(
        StructuredOps.from_partition(sp, dot_dtype=jnp.float32),
        nxc=n, ny=n, nz=n)
    n_loc = 3 * (n + 1) ** 3

    def fn(x, ck, ke, diag_ke, eff, weight):
        data = {"blocks": [{"ck": ck, "Ke": ke, "diag_Ke": diag_ke}],
                "eff": eff, "weight": weight}
        return ops.matvec_local(data, x)

    s = one_chip
    compiled = jax.jit(fn).lower(
        _sds((1, n_loc), s), _sds((1, n, n * (n + 1) - 1), s),
        _sds((24, 24), s),
        _sds((24,), s), _sds((1, n_loc), s), _sds((1, n_loc), s)).compile()
    mem = compiled.memory_analysis()
    # the f32 operands of one matvec fit one chip's 16 GB many times over
    assert mem is None or mem.argument_size_in_bytes < 2 * 2**30
    assert "tpu_custom_call" not in compiled.as_text()


def test_structured_f32_matvec_is_lane_dense_for_v5e(one_chip):
    """The gse f32 slab matvec at cube.weak4's per-chip shape (38 x 150 x
    150 cells) runs on the flattened (y, z) node plane: no intermediate
    keeps the 150-cell (151-node) z row as its minor axis, which pads to
    256 lanes, and the compiler's bytes of one matvec stay near the flat
    form's ~0.98e9 (the 5D grid reads ~1.59e9)."""
    import dataclasses
    import re

    import jax
    import jax.numpy as jnp

    from pcg_mpi_solver_tpu.models import make_cube_model
    from pcg_mpi_solver_tpu.parallel.structured import (
        StructuredOps, partition_structured)

    nx, ny, nz = 38, 150, 150
    sp = partition_structured(make_cube_model(4, 4, 4), 1)
    ops = dataclasses.replace(
        StructuredOps.from_partition(sp, dot_dtype=jnp.float32),
        nxc=nx, ny=ny, nz=nz)
    n_loc = 3 * (nx + 1) * (ny + 1) * (nz + 1)

    def fn(x, ck, ke):
        return ops.matvec_local({"blocks": [{"ck": ck, "Ke": ke}]}, x)

    s = one_chip
    compiled = jax.jit(fn).lower(
        _sds((1, n_loc), s), _sds((1, nx, ny * (nz + 1) - 1), s),
        _sds((24, 24), s)).compile()
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    assert cost["bytes accessed"] <= 1.10e9
    minor = {int(m) for m in re.findall(r"\[(?:\d+,)*(\d+)\]",
                                         compiled.as_text())}
    assert not minor & {nz, nz + 1}


def test_hybrid_f32_matvec_is_scatter_free_for_v5e(one_chip):
    """The hybrid f32 matvec at the benchmark octree's rehearsal size
    (n0 = 2, as benchmarks/configs/octree_graded.json pins it): the
    element->node accumulation of the level slots and the transition
    rows compiles to gathers only — no scatter survives the TPU
    compiler."""
    import jax
    import jax.numpy as jnp

    from pcg_mpi_solver_tpu.models.octree import make_octree_model
    from pcg_mpi_solver_tpu.parallel.hybrid import (
        HybridOps, device_data_hybrid, partition_hybrid)

    model = make_octree_model(nx0=2, ny0=2, nz0=2, h0=1.0, max_level=4,
                              E=3e10, nu=0.2, rho=1.0, load="traction",
                              load_value=1.0, n_incl=6, incl_stiff=10.0,
                              seed=2)
    hp = partition_hybrid(model, 1)
    ops = HybridOps.from_hybrid(hp, dot_dtype=jnp.float32)
    assert ops.combine == "gather"
    data = jax.tree.map(
        lambda a: _sds(a.shape, one_chip, a.dtype),
        device_data_hybrid(hp, jnp.float32))
    text = jax.jit(ops.matvec_local).lower(
        data, _sds((1, hp.pm.n_loc), one_chip)).compile().as_text()
    assert "gather(" in text and "scatter(" not in text
