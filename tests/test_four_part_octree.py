"""A graded octree over four parts on the hybrid backend: the set-up
stages the solver's data on the host and places each part's rows on its
own device, the mixed f32 shadow shares the index arrays with the f64
tree, the partition's balance gauges, the answer against one part, and
the interface assembly's three scopes."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pcg_mpi_solver_tpu import RunConfig, SolverConfig
from pcg_mpi_solver_tpu.models.octree import make_octree_model
from pcg_mpi_solver_tpu.models.synthetic import make_cube_model
from pcg_mpi_solver_tpu.obs.metrics import MetricsRecorder
from pcg_mpi_solver_tpu.ops.matvec import (
    build_bucketed_blocks, device_data, with_f32_shadow)
from pcg_mpi_solver_tpu.parallel import make_mesh
from pcg_mpi_solver_tpu.parallel.hybrid import (
    device_data_hybrid, partition_hybrid)
from pcg_mpi_solver_tpu.parallel.partition import partition_model
from pcg_mpi_solver_tpu.parallel.structured import (
    device_data_structured, partition_structured)
from pcg_mpi_solver_tpu.solver import Solver


@pytest.fixture(scope="module")
def model():
    return make_octree_model(2, 2, 2, max_level=2, n_incl=2, seed=3,
                             load="traction", load_value=1.0)


def _solver(model, n_parts, tol=1e-7, recorder=None):
    cfg = RunConfig(solver=SolverConfig(tol=tol, max_iter=4000,
                                        precision_mode="mixed"))
    return Solver(model, cfg, mesh=make_mesh(n_parts), n_parts=n_parts,
                  backend="hybrid", recorder=recorder)


@pytest.fixture(scope="module")
def four(model):
    rec = MetricsRecorder()
    return _solver(model, 4, recorder=rec), rec


def _leaves_with_paths(tree):
    return jax.tree_util.tree_flatten_with_path(tree)[0]


def _is_float(a) -> bool:
    return np.issubdtype(a.dtype, np.floating)


def test_device_data_is_host_arrays(model):
    hp = partition_hybrid(model, 4)
    pm = partition_model(model, 4)
    sp = partition_structured(make_cube_model(4, 3, 3), 2)
    trees = [device_data(pm), device_data(pm, blocks=False),
             device_data_hybrid(hp, jnp.float32),
             device_data_structured(sp), build_bucketed_blocks(pm)]
    for tree in trees:
        leaves = jax.tree.leaves(tree)
        assert leaves
        for leaf in leaves:
            assert type(leaf) is np.ndarray, type(leaf)


def test_f32_shadow_is_the_device_cast_bit_for_bit(model):
    """The host cast rounds every float leaf as the device's convert
    did, and leaves every other leaf the same object."""
    data = device_data_hybrid(partition_hybrid(model, 4))
    mixed = with_f32_shadow(data)
    assert mixed["f64"] is data
    n_float = 0
    for (path, a), b in zip(_leaves_with_paths(data),
                            jax.tree.leaves(mixed["f32"])):
        if _is_float(a):
            n_float += 1
            want = np.asarray(jnp.asarray(a).astype(jnp.float32))
            assert b.dtype == np.float32, path
            assert np.array_equal(b.view(np.uint32), want.view(np.uint32)), \
                path
        else:
            assert b is a, path
    assert n_float > 0


def test_every_shard_sits_on_its_own_device(four):
    """After construction each part's rows are on the device of that
    part, equal to the host rows; constants are on every device."""
    s, _ = four
    devices = list(s.mesh.devices.flat)
    host = with_f32_shadow(device_data_hybrid(s.pm))
    placed = jax.tree.leaves(s.data)
    assert len(placed) == len(jax.tree.leaves(host))
    for (path, h), a in zip(_leaves_with_paths(host), placed):
        assert a.sharding.mesh.devices.tolist() == s.mesh.devices.tolist()
        shards = a.addressable_shards
        assert {sh.device for sh in shards} == set(devices), path
        for sh in shards:
            if a.sharding.spec == jax.sharding.PartitionSpec():
                rows = h
            else:
                p = devices.index(sh.device)
                rows = h[p:p + 1]
            assert np.array_equal(np.asarray(sh.data), rows), path


def test_index_leaves_are_shared_between_the_trees(four):
    s, _ = four
    shared = 0
    for (path, a), b in zip(_leaves_with_paths(s.data["f64"]),
                            jax.tree.leaves(s.data["f32"])):
        if _is_float(a):
            assert b.dtype == jnp.float32, path
        else:
            shared += 1
            assert b is a, path
    assert shared > 0


def test_balance_gauges_count_the_partition(four, model):
    """The gauges against counts taken from the partition's global ids."""
    s, rec = four
    pm = s.pm
    gid = pm.dof_gid
    real = gid >= 0
    parts_of = np.bincount(gid[real], minlength=pm.glob_n_dof)
    entries = [int((parts_of[gid[p][real[p]]] > 1).sum())
               for p in range(pm.n_parts)]
    assert max(entries) > 0
    assert rec.gauges["comm.iface_entries_max"] == max(entries)
    dofs = real.sum(axis=1)
    assert rec.gauges["part.dofs_max_over_mean"] == pytest.approx(
        dofs.max() / dofs.mean(), rel=1e-12)
    elems = [int((pm.elem_part == p).sum()) for p in range(pm.n_parts)]
    assert rec.gauges["part.elems_max_over_mean"] == pytest.approx(
        max(elems) / np.mean(elems), rel=1e-12)
    one = MetricsRecorder()
    _solver(model, 1, recorder=one)
    assert one.gauges["comm.iface_entries_max"] == 0
    assert one.gauges["part.dofs_max_over_mean"] == 1.0


def test_four_parts_answer_as_one(model):
    """The assembled four-part solve gives the one-part answer."""
    u = {}
    for n_parts in (1, 4):
        s = _solver(model, n_parts, tol=1e-12)
        r = s.step(1.0)
        assert r.flag == 0, n_parts
        u[n_parts] = s.displacement_global()
    assert np.linalg.norm(u[4] - u[1]) <= 1e-9 * np.linalg.norm(u[1])


def test_halo_scopes_in_a_four_part_f32_matvec(four):
    """``mv/halo/pack``, ``mv/halo/psum`` and ``mv/halo/unpack`` label the
    interface assembly of a lowered four-part f32 matvec, the
    all-reduce under ``psum``."""
    s, _ = four
    mv = jax.shard_map(s.ops32.matvec, mesh=s.mesh,
                       in_specs=(s._specs["f32"], s._part_spec),
                       out_specs=s._part_spec, check_vma=False)
    x = jnp.zeros((s.pm.n_parts, s.pm.n_loc), jnp.float32)
    text = jax.jit(mv).lower(s.data["f32"], x).compile().as_text()
    for scope in ("pack", "psum", "unpack"):
        assert f"mv/halo/{scope}/" in text, scope
    reduces = [ln for ln in text.splitlines()
               if re.search(r"= \S+ all-reduce(-start)?\(", ln)]
    assert reduces and all("mv/halo/psum/" in ln for ln in reduces)
