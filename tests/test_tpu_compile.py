"""Chipless compiles of the main path's kernels for a described TPU v5e.

The installed libtpu compiles for a chip that is described, not
attached (``jax.experimental.topologies``): what XLA would
refuse on the chip is refused here, at no chip time.  The topology is
described only inside the module fixture — never at import, in a
``skipif``/``parametrize`` or in conftest.py — so that under xdist only
the worker given this file loads libtpu, and every worker collects the
same tests.

Shapes: the slab matvec at a 40^3 cube, on one chip and per chip of
four, and the hybrid level stencil at the single-brick levels of the
bench octree at n0=12 and n0=8 (chip_smoke.py's phase (b)), level 4, and
the 8^3 block; the f32 slab matvec also runs at the 150^3 flagship size.
A fused Pallas matvec kernel was compiled here at the same shapes until
it was deleted, having never won a measurement on the chip.
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


def _slab_ops(cells):
    """StructuredOps of an (nx, ny, nz) one-part slab, for shapes only."""
    import dataclasses

    import jax.numpy as jnp

    from pcg_mpi_solver_tpu.models import make_cube_model
    from pcg_mpi_solver_tpu.parallel.structured import (
        StructuredOps, partition_structured)

    nx, ny, nz = cells
    sp = partition_structured(make_cube_model(4, 4, 4), 1)
    return dataclasses.replace(
        StructuredOps.from_partition(sp, dot_dtype=jnp.float32),
        nxc=nx, ny=ny, nz=nz)


@pytest.mark.parametrize("cells", [(40, 40, 40), (10, 40, 40)],
                         ids=["cube40_one_chip", "cube40_slab_of_four"])
def test_f32_slab_matvec_compiles_for_v5e(one_chip, cells):
    """The XLA f32 slab matvec (flat (y, z) plane) at a 40^3 cube."""
    import jax

    nx, ny, nz = cells
    ops = _slab_ops(cells)
    n_loc = 3 * (nx + 1) * (ny + 1) * (nz + 1)

    def fn(x, ck, ke):
        return ops.matvec_local({"blocks": [{"ck": ck, "Ke": ke}]}, x)

    text = jax.jit(fn).lower(
        _sds((1, n_loc), one_chip), _sds((1, nx, ny * (nz + 1) - 1), one_chip),
        _sds((24, 24), one_chip)).compile().as_text()
    assert "dot(" in text or "convolution(" in text
    assert "tpu_custom_call" not in text


@pytest.mark.parametrize("cells", [(22, 24, 24), (16, 16, 16), (8, 8, 8)],
                         ids=["level_22x24x24", "level_16", "block_8"])
def test_f32_level_stencil_compiles_for_v5e(one_chip, cells):
    """The hybrid level stencil in f32 on one level grid, as the matvec
    runs it on the part*block batch."""
    import jax
    import jax.numpy as jnp

    from pcg_mpi_solver_tpu.parallel.hybrid import HybridOps

    ops = HybridOps(n_loc=3, n_iface=0, dot_dtype=jnp.float32)
    nodes = (1, 3) + tuple(c + 1 for c in cells)
    text = jax.jit(ops._stencil).lower(
        _sds((24, 24), one_chip), _sds((1,) + cells, one_chip),
        _sds(nodes, one_chip)).compile().as_text()
    assert "dot(" in text or "convolution(" in text
    assert "tpu_custom_call" not in text


def test_structured_f32_matvec_program_compiles_for_v5e(one_chip):
    """The XLA gse slab matvec in f32 at the 150^3 flagship, the form
    every structured f32 iteration runs."""
    import jax

    n = 150
    ops = _slab_ops((n, n, n))
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
    data = jax.tree.map(
        lambda a: _sds(a.shape, one_chip, a.dtype),
        device_data_hybrid(hp, jnp.float32))
    text = jax.jit(ops.matvec_local).lower(
        data, _sds((1, hp.pm.n_loc), one_chip)).compile().as_text()
    assert "gather(" in text and "scatter(" not in text
