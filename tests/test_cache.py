"""Warm-path subsystem (cache/): content-addressed partition cache, AOT
step export, donated-carry dispatch.

The contract under test (about a minute of flagship partitioning and
400+ s octree compiles once had to fit a 9-minute chip window): the SECOND
solve of the same model/n_parts/backend with a warm cache dir must perform
ZERO partitioning work (parallel/partition.py BUILD_CALLS counters) and
ZERO jit tracing of the PCG step (the host-side ``trace.step`` counter
that runs only while jax traces ``_step``), while producing the same
answer.  Donation is a pure memory optimization: bit-identical on/off.
"""

import os
import subprocess
import sys
import tempfile
import warnings

import numpy as np
import pytest

from pcg_mpi_solver_tpu.cache import keys as ckeys
from pcg_mpi_solver_tpu.cache import partition_cache as pcache
from pcg_mpi_solver_tpu.config import (RunConfig, SolverConfig,
                                       TimeHistoryConfig)
from pcg_mpi_solver_tpu.models.synthetic import make_cube_model
from pcg_mpi_solver_tpu.obs.metrics import MetricsRecorder
from pcg_mpi_solver_tpu.parallel.mesh import make_mesh
from pcg_mpi_solver_tpu.parallel.partition import BUILD_CALLS
from pcg_mpi_solver_tpu.solver.driver import Solver

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir(tmp_path):
    """A per-test cache dir (partitions + AOT steps only)."""
    return str(tmp_path / "warm")


def _cfg(*, cache_dir="", donate=True, mode="direct", ipd=-1, tol=1e-8):
    return RunConfig(
        solver=SolverConfig(tol=tol, max_iter=2000, precision_mode=mode,
                            iters_per_dispatch=ipd, donate_carry=donate),
        time_history=TimeHistoryConfig(time_step_delta=[0.0, 1.0]),
        cache_dir=cache_dir,
    )


def _solver(model, cfg, n_dev=1, recorder=None, **kw):
    return Solver(model, cfg, mesh=make_mesh(n_dev), n_parts=n_dev,
                  recorder=recorder, **kw)


# ----------------------------------------------------------------------
# Keys: content addressing + invalidation (jax-free layer)
# ----------------------------------------------------------------------

def test_partition_key_determinism_and_invalidation(monkeypatch):
    fp = "a" * 64
    base = ckeys.partition_cache_key(fp, n_parts=8, backend="general",
                                     dtype="float64")
    again = ckeys.partition_cache_key(fp, n_parts=8, backend="general",
                                      dtype="float64")
    assert base == again
    # every knob that shapes the partition arrays re-keys the entry
    assert ckeys.partition_cache_key(fp, n_parts=4, backend="general",
                                     dtype="float64") != base
    assert ckeys.partition_cache_key(fp, n_parts=8, backend="general",
                                     dtype="float32") != base
    assert ckeys.partition_cache_key(fp, n_parts=8, backend="hybrid",
                                     dtype="float64") != base
    assert ckeys.partition_cache_key("b" * 64, n_parts=8, backend="general",
                                     dtype="float64") != base
    assert ckeys.partition_cache_key(fp, n_parts=8, backend="general",
                                     dtype="float64", method="graph") != base
    # a code bump (package version or cache schema) invalidates everything
    monkeypatch.setattr(ckeys, "PACKAGE_VERSION", "99.99.dev0")
    assert ckeys.partition_cache_key(fp, n_parts=8, backend="general",
                                     dtype="float64") != base
    monkeypatch.undo()
    monkeypatch.setattr(ckeys, "CACHE_SCHEMA", ckeys.CACHE_SCHEMA + 1)
    assert ckeys.partition_cache_key(fp, n_parts=8, backend="general",
                                     dtype="float64") != base


def test_model_fingerprint_tracks_content():
    m1 = make_cube_model(3, 2, 2, heterogeneous=True)
    m2 = make_cube_model(3, 2, 2, heterogeneous=True)
    assert ckeys.model_fingerprint(m1) == ckeys.model_fingerprint(m2)
    m3 = make_cube_model(3, 2, 2, heterogeneous=True)
    m3.F = np.asarray(m3.F).copy()
    m3.F[0] += 1.0
    assert ckeys.model_fingerprint(m3) != ckeys.model_fingerprint(m1)


def test_cache_modules_import_jax_free():
    """The package __init__ stays jax-free and the cache key/stats layer is
    consulted before the accelerator env is configured — importing it
    must not drag jax in."""
    code = ("import sys; import pcg_mpi_solver_tpu.cache; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr


# ----------------------------------------------------------------------
# Partition cache store: hit/miss/corruption/stats
# ----------------------------------------------------------------------

def test_cached_partition_miss_then_hit(tmp_path):
    rec = MetricsRecorder()
    built = []

    def builder():
        built.append(1)
        return {"arr": np.arange(5)}

    d = str(tmp_path)
    out1 = pcache.cached_partition(d, "k" * 32, builder, recorder=rec)
    assert built == [1] and rec.counters["cache.partition.miss"] == 1
    out2 = pcache.cached_partition(d, "k" * 32, builder, recorder=rec)
    assert built == [1], "hit must not invoke the builder"
    assert rec.counters["cache.partition.hit"] == 1
    np.testing.assert_array_equal(out1["arr"], out2["arr"])


def test_corrupt_entry_is_a_miss(tmp_path):
    d = str(tmp_path)
    key = "c" * 32
    assert pcache.store_partition(d, key, [1, 2, 3])
    path = os.path.join(d, "partition", f"{key}.zpkl")
    with open(path, "wb") as f:
        f.write(b"not a zlib pickle")
    assert pcache.load_partition(d, key) is None
    assert not os.path.exists(path), "corrupt entry must be removed"


def test_cache_stats_and_format(tmp_path):
    d = str(tmp_path)
    pcache.store_partition(d, "s" * 32, np.zeros(16))
    stats = pcache.cache_stats(d)
    assert stats["partition"]["entries"] == 1
    assert stats["partition"]["bytes"] > 0
    assert stats["aot"]["entries"] == 0
    assert "partition" in pcache.format_stats(d)


# ----------------------------------------------------------------------
# End-to-end warm path: zero partition work, zero step tracing
# ----------------------------------------------------------------------

def test_second_solve_warm_zero_partition_zero_tracing(cache_dir):
    model = make_cube_model(4, 3, 3, heterogeneous=True)

    rec_cold = MetricsRecorder()
    s1 = _solver(model, _cfg(cache_dir=cache_dir), n_dev=8,
                 recorder=rec_cold)
    assert s1.setup_cache == "cold"
    assert rec_cold.counters["cache.partition.miss"] >= 1
    r1 = s1.step(1.0)
    assert r1.flag == 0
    u1 = np.asarray(s1.displacement_global())
    calls_after_cold = dict(BUILD_CALLS)

    rec_warm = MetricsRecorder()
    s2 = _solver(model, _cfg(cache_dir=cache_dir), n_dev=8,
                 recorder=rec_warm)
    # zero partitioning work: no builder ran anywhere in parallel/
    assert dict(BUILD_CALLS) == calls_after_cold
    assert rec_warm.counters["cache.partition.hit"] >= 1
    assert "cache.partition.miss" not in rec_warm.counters
    assert s2.setup_cache == "warm"
    # zero jit tracing of the PCG step: the AOT entry was deserialized
    # (trace.step increments only inside a live trace of _step)
    assert rec_warm.counters.get("trace.step", 0) == 0
    assert rec_warm.counters.get("cache.aot.hit", 0) == 1
    r2 = s2.step(1.0)
    assert rec_warm.counters.get("trace.step", 0) == 0
    assert r2.flag == 0 and r2.iters == r1.iters
    np.testing.assert_array_equal(np.asarray(s2.displacement_global()), u1)


def test_hybrid_warm_path_recovers_elem_part(cache_dir):
    """Hybrid+mixed needs TWO consistent partitions (level-grid + the
    f64-refresh general partition on the SAME element->part map).  A
    cache hit skips make_elem_part entirely — the driver recovers the
    map from the cached partition itself; warm must be zero-build and
    answer-identical to cold."""
    from pcg_mpi_solver_tpu.models.octree import make_octree_model

    model = make_octree_model(2, 2, 2, max_level=2, n_incl=2, seed=3,
                              load="traction", load_value=1.0)
    cfg = _cfg(cache_dir=cache_dir, mode="mixed")
    rec_cold = MetricsRecorder()
    s1 = Solver(model, cfg, mesh=make_mesh(4), n_parts=4,
                backend="hybrid", recorder=rec_cold)
    assert s1.f64_refresh in ("general", "bucketed")
    assert rec_cold.counters["cache.partition.miss"] >= 2
    r1 = s1.step(1.0)
    assert r1.flag == 0
    calls_after_cold = dict(BUILD_CALLS)

    rec_warm = MetricsRecorder()
    s2 = Solver(model, _cfg(cache_dir=cache_dir, mode="mixed"),
                mesh=make_mesh(4), n_parts=4, backend="hybrid",
                recorder=rec_warm)
    assert dict(BUILD_CALLS) == calls_after_cold
    assert rec_warm.counters["cache.partition.hit"] >= 2
    assert "cache.partition.miss" not in rec_warm.counters
    assert s2.setup_cache == "warm"
    r2 = s2.step(1.0)
    assert r2.flag == 0 and r2.iters == r1.iters
    assert np.array_equal(np.asarray(s2.displacement_global()),
                          np.asarray(s1.displacement_global()))


def test_version_bump_invalidates_on_disk_entries(cache_dir, monkeypatch):
    model = make_cube_model(3, 2, 2, heterogeneous=True)
    rec1 = MetricsRecorder()
    _solver(model, _cfg(cache_dir=cache_dir), recorder=rec1)
    assert rec1.counters["cache.partition.miss"] >= 1

    rec2 = MetricsRecorder()
    _solver(model, _cfg(cache_dir=cache_dir), recorder=rec2)
    assert rec2.counters["cache.partition.hit"] >= 1

    # a package-version bump re-keys every entry: back to a miss
    monkeypatch.setattr(ckeys, "PACKAGE_VERSION", "99.99.dev0")
    rec3 = MetricsRecorder()
    _solver(model, _cfg(cache_dir=cache_dir), recorder=rec3)
    assert rec3.counters["cache.partition.miss"] >= 1
    assert "cache.partition.hit" not in rec3.counters


def test_changed_n_parts_is_a_miss(cache_dir):
    model = make_cube_model(3, 2, 2, heterogeneous=True)
    rec1 = MetricsRecorder()
    _solver(model, _cfg(cache_dir=cache_dir), n_dev=1, recorder=rec1)
    rec2 = MetricsRecorder()
    _solver(model, _cfg(cache_dir=cache_dir), n_dev=8, recorder=rec2)
    assert rec2.counters["cache.partition.miss"] >= 1
    assert "cache.partition.hit" not in rec2.counters


def test_changed_dtype_is_a_miss(cache_dir):
    model = make_cube_model(3, 2, 2, heterogeneous=True)
    cfg32 = _cfg(cache_dir=cache_dir)
    cfg32.solver.dtype = "float32"
    rec1 = MetricsRecorder()
    _solver(model, _cfg(cache_dir=cache_dir), recorder=rec1)
    rec2 = MetricsRecorder()
    _solver(model, cfg32, recorder=rec2)
    assert rec2.counters["cache.partition.miss"] >= 1
    assert "cache.partition.hit" not in rec2.counters


# ----------------------------------------------------------------------
# AOT export roundtrip (CPU backend)
# ----------------------------------------------------------------------

def test_aot_cached_step_roundtrip(tmp_path):
    import jax
    import jax.numpy as jnp

    from pcg_mpi_solver_tpu.cache import aot

    fn = jax.jit(lambda x: x * 2.0 + 1.0)
    abstract = (jax.ShapeDtypeStruct((8,), jnp.float32),)
    rec = MetricsRecorder()
    d = str(tmp_path)
    exp_cold = aot.cached_step(d, "k1", fn, abstract, recorder=rec)
    assert exp_cold is not None
    assert rec.counters["cache.aot.miss"] == 1
    exp_warm = aot.cached_step(d, "k1", fn, abstract, recorder=rec)
    assert rec.counters["cache.aot.hit"] == 1
    x = jnp.arange(8, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(jax.jit(exp_warm.call)(x)),
                                  np.asarray(fn(x)))


def test_aot_corrupt_entry_quarantined_and_rebuilt(tmp_path):
    """A corrupt/truncated serialized AOT step is a cache MISS, not a
    crash: the bad blob is quarantined (<entry>.corrupt — kept for
    toolchain-skew forensics, matching partition_cache's corrupt-entry
    handling) and the step is re-exported in place (ISSUE 3 satellite;
    regression for a truncated file from a killed writer / torn disk)."""
    import jax
    import jax.numpy as jnp

    from pcg_mpi_solver_tpu.cache import aot

    fn = jax.jit(lambda x: x * 3.0)
    abstract = (jax.ShapeDtypeStruct((8,), jnp.float32),)
    rec = MetricsRecorder()
    d = str(tmp_path)
    assert aot.cached_step(d, "kq", fn, abstract, recorder=rec) is not None
    entry = os.path.join(d, "aot", "kq.jaxexport")
    assert os.path.exists(entry)

    # truncate the entry to half its bytes (a killed writer's artifact)
    blob = open(entry, "rb").read()
    with open(entry, "wb") as f:
        f.write(blob[: len(blob) // 2])
    exp = aot.cached_step(d, "kq", fn, abstract, recorder=rec)
    assert exp is not None                      # rebuilt, not crashed
    assert rec.counters["cache.aot.corrupt"] == 1
    assert rec.counters["cache.aot.miss"] == 2  # the corrupt read = miss
    assert os.path.exists(entry + ".corrupt")   # quarantined for forensics
    assert os.path.exists(entry)                # fresh export in place
    x = jnp.arange(8, dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(jax.jit(exp.call)(x)),
                                  np.asarray(fn(x)))

    # a zero-byte entry (torn write) reads the same way
    with open(entry, "wb"):
        pass
    assert aot.cached_step(d, "kq", fn, abstract, recorder=rec) is not None
    assert rec.counters["cache.aot.corrupt"] == 2


def test_aot_quarantine_is_lru_evicted(tmp_path, monkeypatch):
    """Quarantined .corrupt blobs share the LRU discipline (own suffix):
    version bumps re-key entries, so per-key overwrite alone would let
    them grow a long-lived shared cache dir unboundedly."""
    import jax
    import jax.numpy as jnp

    from pcg_mpi_solver_tpu.cache import aot

    d = str(tmp_path)
    fn = jax.jit(lambda x: x * 3.0)
    abstract = (jax.ShapeDtypeStruct((8,), jnp.float32),)
    assert aot.cached_step(d, "kold", fn, abstract) is not None
    old = os.path.join(d, "aot", "kold.jaxexport")
    with open(old, "wb") as f:
        f.write(b"garbage")
    assert aot.load_step(d, "kold") is None     # -> kold.jaxexport.corrupt
    assert os.path.exists(old + ".corrupt")
    monkeypatch.setenv("PCG_TPU_CACHE_GB", str(1 / 2**30))  # ~1 byte cap
    assert aot.cached_step(d, "knew", fn, abstract) is not None
    assert not os.path.exists(old + ".corrupt")


def test_compilation_cache_honours_env_and_sets_nothing(monkeypatch,
                                                       tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the helper uses it and sets
    no jax config in code (jax reads the variable itself)."""
    import jax

    from pcg_mpi_solver_tpu.cache import aot

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert aot.use_compilation_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compilation_cache_defaults_to_fixed_repo_path(monkeypatch):
    """Unset, the cache is <repo>/.jax_cache: one fixed path (the path
    is part of what a later run must find), never a temporary or
    pid-derived one, whatever cache_dir a Solver was given."""
    import jax

    from pcg_mpi_solver_tpu.cache import aot

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert aot.use_compilation_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        # restored before any compile: the cache module stays unarmed
        jax.config.update("jax_compilation_cache_dir", before)
    assert str(os.getpid()) not in want
    assert not want.startswith(tempfile.gettempdir())


def test_cpu_solver_with_cache_dir_places_no_xla_cache(tmp_path):
    """cache_dir keeps the partition/AOT caches only: a CPU Solver
    leaves jax's compilation cache where it was."""
    import jax

    before = jax.config.jax_compilation_cache_dir
    model = make_cube_model(3, 2, 2, heterogeneous=True)
    Solver(model, _cfg(cache_dir=str(tmp_path)), mesh=make_mesh(1),
           n_parts=1)
    assert jax.config.jax_compilation_cache_dir == before
    assert not os.path.exists(os.path.join(str(tmp_path), "xla"))


def test_aot_store_failure_leaves_no_tmp(tmp_path):
    from pcg_mpi_solver_tpu.cache import aot

    class Unserializable:
        def serialize(self):
            raise RuntimeError("disk on fire")

    d = str(tmp_path)
    assert aot.store_step(d, "k" * 32, Unserializable()) is False
    leftovers = [fn for _r, _d, fns in os.walk(d) for fn in fns]
    assert leftovers == [], f"tmp residue: {leftovers}"


def test_aot_entries_lru_evicted(tmp_path, monkeypatch):
    """aot/ honors the same PCG_TPU_CACHE_GB cap as partition/ — code or
    version re-keys orphan old exports, which must not pile up on a
    shared warm dir."""
    import jax
    import jax.numpy as jnp

    from pcg_mpi_solver_tpu.cache import aot

    monkeypatch.setenv("PCG_TPU_CACHE_GB", str(4096 / 2**30))  # ~4 KB cap
    d = str(tmp_path)
    exported = aot.export_step(
        jax.jit(lambda x: x + 1),
        (jax.ShapeDtypeStruct((4,), jnp.float32),))
    for i in range(8):
        assert aot.store_step(d, f"key{i:02d}", exported)
    names = sorted(os.listdir(os.path.join(d, "aot")))
    assert len(names) < 8, "size cap never evicted"
    assert "key07.jaxexport" in names, "newest entry must survive"


# ----------------------------------------------------------------------
# Donated-carry dispatch: bit-identical, warning-free
# ----------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["direct", "mixed"])
def test_donation_parity_chunked(mode):
    """Chunked dispatch (the donated resumable carry) with donation on
    must be BIT-identical to donation off — donation only changes buffer
    aliasing, never values."""
    model = make_cube_model(4, 3, 3, heterogeneous=True)
    s_off = _solver(model, _cfg(donate=False, mode=mode, ipd=20))
    s_on = _solver(model, _cfg(donate=True, mode=mode, ipd=20))
    r_off, r_on = s_off.step(1.0), s_on.step(1.0)
    assert r_on.flag == 0 and r_on.iters == r_off.iters
    assert np.array_equal(np.asarray(s_on.displacement_global()),
                          np.asarray(s_off.displacement_global()))


def test_donation_parity_one_shot_multidevice():
    """One-shot path on the 8-device virtual mesh: the donated un_prev
    must not change values, and the run must not raise donation-related
    XLA copy warnings (unusable-donation = the aliasing contract broke)."""
    model = make_cube_model(4, 3, 3, heterogeneous=True)
    s_off = _solver(model, _cfg(donate=False), n_dev=8)
    r_off = s_off.step(1.0)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        s_on = _solver(model, _cfg(donate=True), n_dev=8)
        r_on = s_on.step(1.0)
    donation_warnings = [w for w in caught
                         if "donat" in str(w.message).lower()]
    assert donation_warnings == []
    assert r_on.flag == 0 and r_on.iters == r_off.iters
    assert np.array_equal(np.asarray(s_on.displacement_global()),
                          np.asarray(s_off.displacement_global()))


@pytest.mark.parametrize("mode", ["direct", "mixed"])
def test_donation_parity_chunked_multidevice(mode):
    model = make_cube_model(5, 4, 4, heterogeneous=True)
    s_off = _solver(model, _cfg(donate=False, mode=mode, ipd=25), n_dev=8)
    s_on = _solver(model, _cfg(donate=True, mode=mode, ipd=25), n_dev=8)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        r_on = s_on.step(1.0)
    assert [w for w in caught if "donat" in str(w.message).lower()] == []
    r_off = s_off.step(1.0)
    assert r_on.flag == 0 and r_on.iters == r_off.iters
    assert np.array_equal(np.asarray(s_on.displacement_global()),
                          np.asarray(s_off.displacement_global()))


def test_failed_donating_step_leaves_solver_retryable():
    """A one-shot dispatch failure with donation on must not strand the
    solver on a deleted un buffer: step() restores a live zero state on
    the exception path, so a retry behaves like the pre-donation code."""
    model = make_cube_model(3, 2, 2, heterogeneous=True)
    s = _solver(model, _cfg(donate=True))
    good_fn = s._step_fn

    def boom(*a, **k):
        raise RuntimeError("injected dispatch failure")

    s._step_fn = boom
    with pytest.raises(RuntimeError, match="injected"):
        s.step(1.0)
    np.asarray(s.un)                    # state is live, not deleted
    s._step_fn = good_fn
    r = s.step(1.0)
    assert r.flag == 0


# ----------------------------------------------------------------------
# Warmup: pre-bake without solving
# ----------------------------------------------------------------------

def test_warmup_populates_caches_and_leaves_state(cache_dir):
    model = make_cube_model(4, 3, 3, heterogeneous=True)
    s = _solver(model, _cfg(cache_dir=cache_dir), n_dev=8)
    un_before = np.asarray(s.un)
    s.warmup()
    np.testing.assert_array_equal(np.asarray(s.un), un_before)
    stats = pcache.cache_stats(cache_dir)
    assert stats["partition"]["entries"] >= 1
    assert stats["aot"]["entries"] >= 1
    # a fresh solver is fully warm after warmup alone (no solve ran)
    rec = MetricsRecorder()
    s2 = _solver(model, _cfg(cache_dir=cache_dir), n_dev=8, recorder=rec)
    assert s2.setup_cache == "warm"
    assert rec.counters.get("trace.step", 0) == 0
    assert s2.step(1.0).flag == 0


def test_warmup_chunked_path(cache_dir):
    """Chunked engine warmup: every budget-loop program compiles (1-iter
    budget execution), and a later real solve on the same solver is
    unaffected — same answer as an un-warmed reference."""
    model = make_cube_model(4, 3, 3, heterogeneous=True)
    ref = _solver(model, _cfg(mode="mixed", ipd=20))
    r_ref = ref.step(1.0)
    rec = MetricsRecorder()
    s = _solver(model, _cfg(cache_dir=cache_dir, mode="mixed", ipd=20),
                recorder=rec)
    s.warmup()
    # warmup paid every compile under the run()-time dispatch names...
    cold_after_warmup = {k: v["cold_s"]
                         for k, v in rec.dispatch_stats().items()}
    assert {"start", "inner_start", "inner_cycle"} <= \
        cold_after_warmup.keys()
    r = s.step(1.0)
    # ...so the real solve's dispatches all book WARM (no new cold time)
    for name, st in rec.dispatch_stats().items():
        assert st["cold_s"] == cold_after_warmup.get(name), name
    assert r.flag == 0 and r.iters == r_ref.iters
    assert np.array_equal(np.asarray(s.displacement_global()),
                          np.asarray(ref.displacement_global()))
