"""Contract-lint subsystem (analysis/): engine semantics, and one
deliberately-violating fixture per rule proving each rule actually FIRES
— an extra psum over budget, a callback / oversized folded constant in a
loop body, an f64 leak in an f32 program, a dropped donation, and a
config field omitted from the cache key / snapshot fingerprint (the
PR-5/PR-6 review-hardening bug class, now a mechanical failure).

The current tree must be CLEAN: tier-1 runs the fast lint in-process;
the full pass (donation + fingerprint sweeps, ~30 s) is `slow`-marked
and exercised by `pcg-tpu lint` / hw_session step 0.
"""

import json
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pcg_mpi_solver_tpu.analysis import engine
from pcg_mpi_solver_tpu.analysis.engine import Finding, apply_baseline
from pcg_mpi_solver_tpu.analysis.programs import DonationSurface, Program
from pcg_mpi_solver_tpu.analysis.rules_jaxpr import (
    check_collective_budget, check_dtype_discipline, check_hot_loop_purity)
from pcg_mpi_solver_tpu.parallel.mesh import PARTS_AXIS, make_mesh


# ---------------------------------------------------------------------------
# synthetic Program fixtures
# ---------------------------------------------------------------------------

def _toy_program(body_fn, budget, role="f64", dtype=jnp.float64,
                 n_trips=3, variant="classic", width=8):
    """A 2-part shard_map'd while-loop program, traced like the real
    canonical matrix entries."""
    mesh = make_mesh(2)
    P = jax.sharding.PartitionSpec(PARTS_AXIS)

    def prog(x):
        def cond(c):
            return c[0] < n_trips

        return jax.lax.while_loop(cond, body_fn, (0, x))[1]

    fn = jax.jit(jax.shard_map(prog, mesh=mesh, in_specs=(P,),
                               out_specs=P, check_vma=False))
    jx = jax.make_jaxpr(fn)(jnp.zeros((2, width), dtype))
    return Program(name="toy", backend="general", variant=variant,
                   nrhs=1, role=role, jaxpr=jx,
                   collective_budget=budget, n_iface=1)


def _body_psums(n):
    def body(c):
        i, v = c
        for _ in range(n):
            v = v + jax.lax.psum(v, PARTS_AXIS)
        return i + 1, v

    return body


# ---------------------------------------------------------------------------
# rule: collective-budget
# ---------------------------------------------------------------------------

def test_collective_budget_clean_within_budget():
    prog = _toy_program(_body_psums(2), {"psum": 2})
    assert check_collective_budget(prog) == []


def test_collective_budget_fires_on_extra_psum():
    """The seeded violation: one psum beyond the declared budget — the
    exact 'silently re-serialized reduction' regression."""
    prog = _toy_program(_body_psums(3), {"psum": 2})
    findings = check_collective_budget(prog)
    assert len(findings) == 1
    assert findings[0].rule == "collective-budget"
    assert "'psum': 3" in findings[0].message


def test_collective_budget_fires_on_undercounted_budget():
    """An UNDER-count fails too: the declaration (and the comm.* gauges
    reading the same table) would advertise collectives that no longer
    exist."""
    prog = _toy_program(_body_psums(1), {"psum": 2})
    assert check_collective_budget(prog) != []


def test_collective_budget_fires_on_undeclared_collective_kind():
    def body(c):
        i, v = c
        v = v + jax.lax.psum(v, PARTS_AXIS)
        v = jax.lax.ppermute(v, PARTS_AXIS, [(0, 1), (1, 0)])
        return i + 1, v

    prog = _toy_program(body, {"psum": 1})
    findings = check_collective_budget(prog)
    assert findings and "ppermute" in findings[0].message


def test_budget_table_matches_comm_estimate_gauges():
    """The gauges and the proof read ONE table (ops/matvec.py): body
    budget = advertised healthy-iteration psums + the deferred check."""
    from pcg_mpi_solver_tpu.ops.matvec import (
        Ops, PCG_DEFERRED_CHECK_PSUMS)

    from pcg_mpi_solver_tpu.config import PCG_VARIANTS

    ops = Ops(n_loc=8, n_iface=4)
    for variant in PCG_VARIANTS:
        gauge = ops.comm_estimate(variant=variant)["psums_per_iter"]
        budget = ops.body_collective_budget(variant)["psum"]
        assert budget == gauge + PCG_DEFERRED_CHECK_PSUMS
    # pipelined's contract: ONE scalar psum, same count as fused —
    # the win is overlap (psum-overlap rule), not fewer collectives
    assert ops.comm_estimate(variant="pipelined")["psums_per_iter"] == \
        ops.comm_estimate(variant="fused")["psums_per_iter"]
    with pytest.raises(KeyError):
        ops.body_collective_budget("frobnicate")  # unknown variant: loud
    with pytest.raises(KeyError):
        ops.comm_estimate(variant="frobnicate")


# ---------------------------------------------------------------------------
# rule: psum-overlap (ISSUE 11)
# ---------------------------------------------------------------------------

def _overlapped_body(c):
    """Pipelined-shaped toy: the scalar psum reads only carry state and
    nothing downstream of it feeds the 'stencil' psum — independent in
    both directions, exactly the GV overlap property.  The trailing
    'deferred check' psum consumes the stencil output, like the real
    body (so the stencil collective itself is NOT independent)."""
    i, v = c
    a = jax.lax.psum(jnp.sum(v), PARTS_AXIS)       # overlappable scalar
    b = jax.lax.psum(v, PARTS_AXIS)                # 'stencil' collective
    chk = jax.lax.psum(jnp.sum(b), PARTS_AXIS)     # check reads stencil
    return i + 1, v + b + a + chk


def _serialized_body(c):
    """The regression the rule exists to catch: the scalar reduction
    consumes the stencil collective's output (the fused variant's
    serialization, reintroduced into a body claiming overlap)."""
    i, v = c
    b = jax.lax.psum(v, PARTS_AXIS)
    a = jax.lax.psum(jnp.sum(b), PARTS_AXIS)
    return i + 1, v + b + a


def test_psum_overlap_clean_on_overlapped_pipelined_body():
    from pcg_mpi_solver_tpu.analysis.rules_jaxpr import check_psum_overlap

    prog = _toy_program(_overlapped_body, {"psum": 3},
                        variant="pipelined")
    assert check_psum_overlap(prog) == []


def test_psum_overlap_fires_on_serialized_pipelined_body():
    from pcg_mpi_solver_tpu.analysis.rules_jaxpr import check_psum_overlap

    prog = _toy_program(_serialized_body, {"psum": 2},
                        variant="pipelined")
    findings = check_psum_overlap(prog)
    assert len(findings) == 1 and findings[0].rule == "psum-overlap"
    assert "serialized" in findings[0].message


def test_psum_overlap_fires_on_feeding_direction_too():
    """Serialization in the OTHER direction (the classic shape: the
    reduction's output feeds the stencil collective's operand) must
    fail a pipelined body as well — overlap demands independence both
    ways."""
    from pcg_mpi_solver_tpu.analysis.rules_jaxpr import check_psum_overlap

    def body(c):
        i, v = c
        a = jax.lax.psum(jnp.sum(v), PARTS_AXIS)
        b = jax.lax.psum(v * a, PARTS_AXIS)        # stencil consumes a
        return i + 1, v + b

    prog = _toy_program(body, {"psum": 2}, variant="pipelined")
    assert check_psum_overlap(prog) != []


def test_psum_overlap_negative_control_guards_the_walker():
    """An 'independent' psum showing up in a classic/fused body means
    the dependency walker lost edges — the rule must fail loudly there
    instead of letting the pipelined proof go vacuous."""
    from pcg_mpi_solver_tpu.analysis.rules_jaxpr import check_psum_overlap

    clean = _toy_program(_serialized_body, {"psum": 2}, variant="fused")
    assert check_psum_overlap(clean) == []
    broken = _toy_program(_overlapped_body, {"psum": 3}, variant="fused")
    findings = check_psum_overlap(broken)
    assert findings and "walker" in findings[0].message


def test_psum_overlap_rejects_vector_payload_as_the_independent_psum():
    """The one independent psum must be the small stacked scalar
    reduction; a stencil-sized payload that merely lost its consumers
    is not the latency-hiding claim."""
    from pcg_mpi_solver_tpu.analysis.rules_jaxpr import check_psum_overlap

    def body(c):
        i, v = c
        a = jax.lax.psum(v, PARTS_AXIS)            # vector, no consumers
        b = jax.lax.psum(v * 2.0, PARTS_AXIS)
        cden = jax.lax.psum(jnp.sum(b), PARTS_AXIS)
        return i + 1, v + b + cden + jax.lax.stop_gradient(a) * 0.0

    prog = _toy_program(body, {"psum": 3}, variant="pipelined", width=64)
    findings = check_psum_overlap(prog)
    assert findings and "payload" in findings[0].message


def test_psum_overlap_conservative_on_nested_loops():
    """Collectives inside a nested while/scan are marked mutually
    dependent (loop feedback can wire anything to anything) — the safe
    over-approximation: a pipelined body whose only psums live in a
    nested loop proves NOTHING overlappable, rather than vacuously
    passing."""
    from pcg_mpi_solver_tpu.analysis.rules_jaxpr import check_psum_overlap

    def body(c):
        i, v = c

        def inner(j, acc):
            return acc + jax.lax.psum(acc, PARTS_AXIS) \
                + jax.lax.psum(jnp.sum(acc), PARTS_AXIS)

        return i + 1, jax.lax.fori_loop(0, 2, inner, v)

    prog = _toy_program(body, {"psum": 2}, variant="pipelined")
    assert check_psum_overlap(prog) != []


def test_psum_overlap_conservative_on_singleton_nested_loop_psum():
    """The degenerate nested-loop case: a body whose ONLY scalar psum
    sits inside a nested fori_loop.  Mutual marking between nested
    collectives is vacuous for a singleton, so the walker must mark it
    SELF-dependent (its prior trip feeds it through loop carry) — the
    rule fails rather than certifying a serialized-inside-a-loop psum
    as the overlappable reduction."""
    from pcg_mpi_solver_tpu.analysis.rules_jaxpr import check_psum_overlap

    def body(c):
        i, v = c

        def inner(j, acc):
            return acc + jax.lax.psum(jnp.sum(acc), PARTS_AXIS)

        return i + 1, jax.lax.fori_loop(0, 2, inner, v)

    prog = _toy_program(body, {"psum": 1}, variant="pipelined")
    assert check_psum_overlap(prog) != []


# ---------------------------------------------------------------------------
# rule: hot-loop-purity
# ---------------------------------------------------------------------------

def test_hot_loop_purity_clean():
    prog = _toy_program(_body_psums(1), {"psum": 1})
    assert check_hot_loop_purity(prog) == []


def test_hot_loop_purity_fires_on_callback_in_body():
    def body(c):
        i, v = c
        jax.debug.callback(lambda a: None, v.sum())
        return i + 1, v + 1.0

    prog = _toy_program(body, {})
    findings = check_hot_loop_purity(prog)
    assert len(findings) == 1
    assert "debug_callback" in findings[0].message


def test_hot_loop_purity_fires_on_oversized_folded_const():
    """A trace-time-captured operand array feeding the loop (the AOT
    export bloat class)."""
    big = np.arange(100_000, dtype=np.float64)

    def body(c):
        i, v = c
        return i + 1, v + jnp.asarray(big)[:8]

    prog = _toy_program(body, {})
    findings = check_hot_loop_purity(prog)
    assert len(findings) == 1
    assert "folded constant" in findings[0].message
    assert "100000" in findings[0].message


def test_hot_loop_purity_small_consts_pass():
    small = np.arange(8, dtype=np.float64)

    def body(c):
        i, v = c
        return i + 1, v + jnp.asarray(small)

    assert check_hot_loop_purity(_toy_program(body, {})) == []


# ---------------------------------------------------------------------------
# rule: dtype-discipline
# ---------------------------------------------------------------------------

def test_dtype_discipline_fires_on_f64_leak():
    def body(c):
        i, v = c
        y = v.astype(jnp.float64) * 2.0        # the leak
        return i + 1, y.astype(jnp.float32)

    prog = _toy_program(body, {}, role="f32", dtype=jnp.float32)
    findings = check_dtype_discipline(prog)
    assert len(findings) == 1
    assert "float64" in findings[0].message


def test_dtype_discipline_weak_scalars_and_f64_role_exempt():
    def body(c):
        i, v = c
        return i + 1, v * 2.0 + 1.5       # weak python-float literals

    assert check_dtype_discipline(
        _toy_program(body, {}, role="f32", dtype=jnp.float32)) == []
    # f64-role programs are out of scope by construction
    leaky = _toy_program(_body_psums(1), {}, role="f64")
    assert check_dtype_discipline(leaky) == []


# ---------------------------------------------------------------------------
# rule: donation-integrity
# ---------------------------------------------------------------------------

def test_donation_check_passes_on_real_aliasing():
    from pcg_mpi_solver_tpu.analysis.programs import check_donation

    def step(c, y):
        return {"a": c["a"] + y, "b": c["b"] * 2.0}

    c = {"a": jnp.zeros((4, 4)), "b": jnp.ones((4, 4))}
    fn = jax.jit(step, donate_argnums=(0,))
    assert check_donation(DonationSurface(
        "good", fn, (c, jnp.ones((4, 4))), c)) == []


def test_donation_check_fires_on_dropped_donation():
    """The seeded violation: the donated carry has no matching output,
    so jax SILENTLY drops the aliasing — the dispatch copies."""
    from pcg_mpi_solver_tpu.analysis.programs import check_donation

    def step(c, y):
        return y.sum()

    c = {"a": jnp.zeros((4, 4)), "b": jnp.ones((4, 4))}
    fn = jax.jit(step, donate_argnums=(0,))
    errs = check_donation(DonationSurface(
        "bad", fn, (c, jnp.ones((4, 4))), c))
    assert len(errs) == 1
    assert "dropped" in errs[0]


# ---------------------------------------------------------------------------
# rule: fingerprint-completeness
# ---------------------------------------------------------------------------

def test_fingerprint_rule_clean_on_real_surfaces():
    """tol is trace-affecting and covered by BOTH real surfaces."""
    from pcg_mpi_solver_tpu.analysis.rules_config import (
        check_fingerprint_completeness)

    assert check_fingerprint_completeness(fields=["tol"]) == []


def test_fingerprint_rule_catches_field_omitted_from_cache_key():
    """The acceptance fixture: a config field deliberately dropped from
    step_cache_key's payload turns into a mechanical finding."""
    from pcg_mpi_solver_tpu.analysis.rules_config import (
        check_fingerprint_completeness)
    from pcg_mpi_solver_tpu.cache.keys import step_cache_key

    def leaky_key(**kw):
        solver = dict(kw.get("solver") or {})
        solver.pop("tol", None)               # the deliberate omission
        kw["solver"] = solver
        return step_cache_key(**kw)

    findings = check_fingerprint_completeness(fields=["tol"],
                                              key_fn=leaky_key)
    assert len(findings) == 1
    assert "step_cache_key" in findings[0].message
    assert findings[0].loc == "field:SolverConfig.tol"


def test_fingerprint_rule_catches_field_omitted_from_snapshot_fp():
    from pcg_mpi_solver_tpu.analysis.rules_config import (
        check_fingerprint_completeness)

    findings = check_fingerprint_completeness(
        fields=["tol"], fp_fn=lambda solver: {"model": "const"})
    assert len(findings) == 1
    assert "_fingerprint" in findings[0].message


def test_structural_key_components_bite():
    from pcg_mpi_solver_tpu.analysis.rules_config import (
        check_structural_key_components)
    from pcg_mpi_solver_tpu.cache.keys import step_cache_key

    assert check_structural_key_components() == []

    def nrhs_blind(**kw):
        kw["nrhs"] = 1
        return step_cache_key(**kw)

    findings = check_structural_key_components(key_fn=nrhs_blind)
    assert len(findings) == 1
    assert "nrhs" in findings[0].message


def test_runconfig_fields_all_classified(monkeypatch):
    from pcg_mpi_solver_tpu.analysis import rules_config as rc

    assert rc.check_runconfig_classified() == []
    # an unclassified (e.g. freshly added) RunConfig field is a finding
    monkeypatch.setattr(
        rc, "TRACE_NEUTRAL_RUNCONFIG",
        rc.TRACE_NEUTRAL_RUNCONFIG - {"cache_dir"})
    findings = rc.check_runconfig_classified()
    assert len(findings) == 1
    assert "cache_dir" in findings[0].loc


def test_pre_existing_snapshots_resume_across_the_fp_extension(tmp_path):
    """Back-compat: snapshots written BEFORE the fingerprint gained the
    new numerics keys (dot_dtype, max_stag_steps, inner_tol,
    mixed_knobs, trace_len) must still load — the knobs existed but were
    unrecorded, so legacy entries skip the new checks instead of
    mismatching on upgrade (the PR-6 nrhs shim precedent)."""
    from pcg_mpi_solver_tpu.utils.checkpoint import SnapshotStore

    fp = {"model": "m", "nrhs": 1, "dot_dtype": "float64",
          "max_stag_steps": 3, "inner_tol": 1e-5,
          "mixed_knobs": [0, 0, 0.7, 30.0], "trace_len": 0}
    store = SnapshotStore(str(tmp_path), fp)
    store.save(1, {"x": np.zeros(4)})
    # doctor the stored fingerprint back to its pre-extension shape
    path = store._file(1)
    with np.load(path) as z:
        flat = {k: z[k] for k in z.files}
    saved = json.loads(bytes(flat["__fingerprint"]).decode())
    for k in ("dot_dtype", "max_stag_steps", "inner_tol", "mixed_knobs",
              "trace_len"):
        saved.pop(k)
    flat["__fingerprint"] = np.frombuffer(
        json.dumps(saved, sort_keys=True).encode(), dtype=np.uint8).copy()
    np.savez_compressed(path, **flat)
    state = store.load(1)          # legacy entry: must NOT mismatch
    assert state is not None and np.all(state["x"] == 0)
    # a snapshot that DID record the field still fails loudly on drift
    store.save(2, {"x": np.zeros(4)})
    store2 = SnapshotStore(str(tmp_path), dict(fp, max_stag_steps=9))
    with pytest.raises(ValueError, match="max_stag_steps"):
        store2.load(2)


def test_snapshot_fingerprint_carries_the_new_numerics_fields():
    """The gaps this PR's sweep found (dot_dtype, max_stag_steps,
    inner_tol, mixed knobs, trace ring length) are fingerprinted."""
    from pcg_mpi_solver_tpu.analysis.programs import build_solver
    from pcg_mpi_solver_tpu.utils.checkpoint import _fingerprint

    fp = _fingerprint(build_solver("general"))
    for key in ("dot_dtype", "max_stag_steps", "inner_tol",
                "mixed_knobs", "trace_len", "pcg_variant", "nrhs"):
        assert key in fp, key


# ---------------------------------------------------------------------------
# engine: registry, baseline, reports, end-to-end
# ---------------------------------------------------------------------------

def test_rule_catalog_complete():
    rules = {r.id: r for r in engine.list_rules()}
    expected = {"collective-budget", "hot-loop-purity", "dtype-discipline",
                "donation-integrity", "fingerprint-completeness",
                "recovery-paths", "recovery-coverage", "telemetry-schema",
                "cost-model-completeness", "partition-key-components",
                "scope-labels", "doc-schema-sync",
                "serve-admission-events"}
    assert expected <= set(rules)
    assert len(expected) >= 5
    # the pre-hardware-window gate covers the structural claims
    assert rules["collective-budget"].fast
    assert rules["recovery-paths"].fast
    assert rules["recovery-coverage"].fast
    assert rules["cost-model-completeness"].fast
    assert rules["partition-key-components"].fast
    assert rules["scope-labels"].fast
    assert rules["doc-schema-sync"].fast
    assert rules["serve-admission-events"].fast
    assert not rules["fingerprint-completeness"].fast


# ----------------------------------------------------------------------
# scope-labels (ISSUE 15): trace-attribution named scopes in every loop
# ----------------------------------------------------------------------

def test_scope_labels_clean_on_real_programs():
    """Every canonical program (all variants, scalar + blocked) carries
    all four pcg/* phase labels, and the parser-side loudness probe
    passes on the real bucketer."""
    from pcg_mpi_solver_tpu.analysis.programs import build_programs
    from pcg_mpi_solver_tpu.analysis.rules_jaxpr import (
        check_scope_labels, check_unknown_label_loudness)

    for prog in build_programs(fast=True):
        assert check_scope_labels(prog) == [], prog.name
    assert check_unknown_label_loudness() == []


def test_scope_labels_fires_on_missing_label():
    """A label the trace consumer buckets on but no program carries
    (here: a seeded extra phase) must fire per program — a hot loop
    that lost its named scope silently moves its time to 'other'."""
    from pcg_mpi_solver_tpu.analysis.programs import build_programs
    from pcg_mpi_solver_tpu.analysis.rules_jaxpr import (
        check_scope_labels)

    prog = build_programs(fast=True)[0]
    seeded = {"pcg/matvec": "matvec", "pcg/ghost_phase": "ghost"}
    findings = check_scope_labels(prog, phase_scopes=seeded)
    assert len(findings) == 1
    assert "pcg/ghost_phase" in findings[0].message
    assert findings[0].loc == f"program:{prog.name}"
    # ...and a toy program with no scopes at all fires on every label
    toy = _toy_program(_body_psums(1), {"psum": 1})
    all_missing = check_scope_labels(toy)
    assert len(all_missing) == 4


def test_scope_labels_unknown_label_loudness_probe_fires():
    """The probe must catch a bucketer that silently DROPS unbucketable
    time or unknown pcg/* labels (seeded broken implementations)."""
    from pcg_mpi_solver_tpu.analysis.rules_jaxpr import (
        check_unknown_label_loudness)

    def drops_unknowns(ops, scope_map):
        from pcg_mpi_solver_tpu.obs.perf import PHASES

        return {"phases": {ph: {"us": 0.0, "events": 0}
                           for ph in PHASES},
                "other_us": 0.0, "other_events": 0,
                "unknown_scopes": {}}

    findings = check_unknown_label_loudness(bucket_fn=drops_unknowns)
    assert len(findings) == 2       # dropped time AND dropped label
    assert any("DROPPED" in f.message for f in findings)
    assert any("unknown_scopes" in f.message for f in findings)

    def crashes(ops, scope_map):
        raise RuntimeError("boom")

    findings = check_unknown_label_loudness(bucket_fn=crashes)
    assert len(findings) == 1
    assert "crashed" in findings[0].message


# ----------------------------------------------------------------------
# recovery-coverage (ISSUE 9): dispatch surfaces wrapped or exempted
# ----------------------------------------------------------------------

def test_recovery_coverage_clean_on_real_tree():
    from pcg_mpi_solver_tpu.analysis.rules_ast import (
        recovery_coverage_rule)

    assert recovery_coverage_rule(None) == []


def test_recovery_coverage_seeded_violations():
    """Every failure class fires on seeded sources: an unregistered
    Krylov dispatch surface, a registered surface that dropped its
    harness call, an exempt surface without the documented marker, and
    a stale registry entry."""
    from pcg_mpi_solver_tpu.analysis.rules_ast import (
        check_recovery_coverage)

    rel = "pcg_mpi_solver_tpu/solver/driver.py"

    # (1) unregistered surface: a new method opening a terminal span
    src = (
        "class Solver:\n"
        "    def step(self):\n"
        "        # recovery-exempt: test stub\n"
        "        self._step_fn()\n"
        "    def _step_chunked(self):\n"
        "        run_with_recovery()\n"
        "    def _solve_many_chunked(self):\n"
        "        run_many_with_recovery()\n"
        "    def solve_many(self):\n"
        "        return self._dispatch_with_retry('solve_many', f)\n"
        "    def sneaky_new_path(self):\n"
        "        with self._rec.dispatch('many_cycle'):\n"
        "            pass\n")
    errs = check_recovery_coverage({rel: src})
    assert any("sneaky_new_path" in e and "not registered" in e
               for e in errs), errs
    assert not any("_step_chunked" in e for e in errs)

    # (2) registered surface that no longer calls its harness
    src2 = src.replace("run_with_recovery()", "pass")
    errs2 = check_recovery_coverage({rel: src2})
    assert any("_step_chunked" in e and "run_with_recovery" in e
               for e in errs2), errs2

    # (3) exempt surface without the documented marker
    src3 = src.replace("        # recovery-exempt: test stub\n", "")
    errs3 = check_recovery_coverage({rel: src3})
    assert any("`step`" in e and "recovery-exempt" in e
               for e in errs3), errs3

    # (4) stale registry entry: the registered function vanished
    errs4 = check_recovery_coverage({rel: "x = 1\n"})
    assert any("no such function" in e for e in errs4), errs4


# ----------------------------------------------------------------------
# consensus-coverage (ISSUE 18): host-side collectives on the dispatch
# path route their verdicts through parallel/consensus or are exempted
# ----------------------------------------------------------------------

def test_consensus_coverage_clean_on_real_tree():
    from pcg_mpi_solver_tpu.analysis.rules_ast import (
        consensus_coverage_rule)

    assert consensus_coverage_rule(None) == []


def test_consensus_coverage_seeded_violations():
    """Every failure class fires on seeded sources: an unregistered
    collective call site, a registered site that dropped its consensus
    call, an exempt site without the documented marker, and a stale
    registry entry — plus the `warmup` negative control (the unrelated
    compile-warmup method must never register as a collective)."""
    from pcg_mpi_solver_tpu.analysis.rules_ast import (
        check_consensus_coverage)

    rel = "pcg_mpi_solver_tpu/solver/driver.py"
    src = (
        "class Solver:\n"
        "    def __init__(self):\n"
        "        ok = agree_flag(comm, ok)\n"
        "    def _exchange_export_glue(self):\n"
        "        # consensus-exempt: test stub\n"
        "        mh.process_allgather(i)\n"
        "    def solve(self):\n"
        "        # consensus-exempt: test stub\n"
        "        multihost_utils.sync_global_devices('prepared')\n"
        "    def warm_compile(self):\n"
        "        self.engine.warmup()\n"
        "    def sneaky_branch(self):\n"
        "        if comm.allreduce(v, 'min'):\n"
        "            pass\n")

    # (0) clean seeded tree modulo the one unregistered site; warmup
    # must not be flagged
    errs = check_consensus_coverage({rel: src})
    assert any("sneaky_branch" in e and "not registered" in e
               for e in errs), errs
    assert not any("warm_compile" in e for e in errs), errs
    assert not any("__init__" in e for e in errs), errs

    # (2) registered site that no longer calls its consensus primitive
    src2 = src.replace("ok = agree_flag(comm, ok)", "pass")
    errs2 = check_consensus_coverage({rel: src2})
    assert any("__init__" in e and "agree_flag" in e
               for e in errs2), errs2

    # (3) exempt site without the documented marker
    src3 = src.replace(
        "        # consensus-exempt: test stub\n"
        "        multihost_utils.sync_global_devices('prepared')\n",
        "        multihost_utils.sync_global_devices('prepared')\n")
    errs3 = check_consensus_coverage({rel: src3})
    assert any("`solve`" in e and "consensus-exempt" in e
               for e in errs3), errs3

    # (4) stale registry entry: the registered function vanished
    errs4 = check_consensus_coverage({rel: "x = 1\n"})
    assert any("no such function" in e for e in errs4), errs4


# ----------------------------------------------------------------------
# serve-admission-events (ISSUE 19): every admission-decision outcome
# emits its schema-versioned telemetry event
# ----------------------------------------------------------------------

def test_serve_admission_events_clean_on_real_tree():
    from pcg_mpi_solver_tpu.analysis.rules_ast import (
        serve_admission_events_rule)

    assert serve_admission_events_rule(None) == []


def test_serve_admission_events_seeded_violations():
    """Every failure class fires on seeded sources: a decision site
    that dropped its event emission (the silent-outcome regression the
    rule exists for), a stale registry entry, and a registry kind the
    telemetry schema no longer knows."""
    from pcg_mpi_solver_tpu.analysis.rules_ast import (
        ADMISSION_EVENT_SITES, check_admission_events)

    rel = "pcg_mpi_solver_tpu/serve/admission.py"
    src = (
        "class AdmissionController:\n"
        "    def admit(self, spec, now=None):\n"
        "        self._rec.event('job_admit', job=spec['job'])\n"
        "    def _reject(self, job, reason, **fields):\n"
        "        self._rec.event('job_reject', job=job, reason=reason)\n"
        "    def shed_past_deadline(self, now=None):\n"
        "        self._rec.event('job_shed', job='x', reason='r')\n")
    assert check_admission_events({rel: src}) == []

    # (1) a decision site stops emitting: the outcome goes silent
    src1 = src.replace(
        "        self._rec.event('job_shed', job='x', reason='r')\n",
        "        pass\n")
    errs1 = check_admission_events({rel: src1})
    assert any("shed_past_deadline" in e and "`job_shed`" in e
               for e in errs1), errs1

    # (2) stale registry entry: the registered function vanished
    src2 = src.replace("def admit", "def admit_renamed")
    errs2 = check_admission_events({rel: src2})
    assert any("`admit`" in e and "no such function" in e
               for e in errs2), errs2

    # (3) registry kinds must exist in obs/schema EVENT_KINDS — the
    # real registry is checked live against the real schema
    from pcg_mpi_solver_tpu.obs.schema import EVENT_KINDS
    for kinds in ADMISSION_EVENT_SITES.values():
        for kind in kinds:
            assert kind in EVENT_KINDS, kind

    # an emit of the WRONG kind does not satisfy the requirement
    src3 = src.replace("'job_admit'", "'job_done'")
    errs3 = check_admission_events({rel: src3})
    assert any("`admit`" in e and "`job_admit`" in e for e in errs3), errs3


# ----------------------------------------------------------------------
# cost-model-completeness (ISSUE 12): the analytic per-iteration cost
# model covers every canonical variant x precond combination, loudly
# ----------------------------------------------------------------------

def test_cost_model_completeness_clean_on_real_tree():
    from pcg_mpi_solver_tpu.analysis.rules_config import (
        cost_model_completeness_rule)

    assert cost_model_completeness_rule(None) == []


def test_cost_model_completeness_seeded_violations():
    """Every failure class fires on seeded model functions: a combo the
    model cannot produce, a degenerate (zero/partial-phase) entry, an
    unknown name silently accepted, and the wrong exception type for
    an unknown name."""
    from pcg_mpi_solver_tpu.analysis.rules_config import (
        check_cost_model_completeness)
    from pcg_mpi_solver_tpu.obs import perf as _perf

    shape = _perf.ProblemShape(n_dof=10_000, n_parts=4, n_iface=500,
                               elem_groups=((24, 3_000),))

    def real(v, p, r):
        return _perf.cost_model(shape, v, p, r)

    # (0) the real model over the real tables: no findings
    assert check_cost_model_completeness(model_fn=real) == []

    # (1) a canonical combo the model has no entry for (the new-variant-
    # landed-in-one-table-only failure): loud finding naming the combo
    def missing_combo(v, p, r):
        if (v, p) == ("pipelined", "mg"):
            raise KeyError(p)
        return real(v, p, r)

    errs = check_cost_model_completeness(model_fn=missing_combo)
    assert any("pipelined" in f.loc and "mg" in f.loc and
               "no entry" in f.message for f in errs), errs

    # (2) a degenerate entry: a dropped phase or a zero prediction must
    # read as a finding, not as "this phase is free"
    def dropped_phase(v, p, r):
        cm = dict(real(v, p, r))
        cm["phases"] = {k: val for k, val in cm["phases"].items()
                        if k != "axpy"}
        return cm

    errs2 = check_cost_model_completeness(model_fn=dropped_phase)
    assert any("degenerate" in f.message and "axpy" in f.message
               for f in errs2), errs2

    def zero_pred(v, p, r):
        return {**real(v, p, r), "predicted_ms_per_iter": 0.0}

    errs3 = check_cost_model_completeness(model_fn=zero_pred)
    assert any("degenerate" in f.message for f in errs3), errs3

    # (3) unknown names silently accepted: the fabricated-prediction
    # failure the loudness probes exist for
    def silent_default(v, p, r):
        try:
            return real(v, p, r)
        except KeyError:
            return real("classic", "jacobi", r)

    errs4 = check_cost_model_completeness(model_fn=silent_default)
    assert any(f.loc == "probe:unknown-variant" and "silently" in
               f.message for f in errs4), errs4
    assert any(f.loc == "probe:unknown-precond" for f in errs4), errs4

    # (4) the wrong exception type: consumers catch KeyError as the
    # table-out-of-sync signal, anything else is an internal failure
    def wrong_exc(v, p, r):
        try:
            return real(v, p, r)
        except KeyError:
            raise ValueError(f"{v}/{p}")

    errs5 = check_cost_model_completeness(model_fn=wrong_exc)
    assert any("instead of KeyError" in f.message for f in errs5), errs5


def test_baseline_suppression_and_undocumented_entry():
    f1 = Finding(rule="r", loc="a", message="m")
    f2 = Finding(rule="r", loc="b", message="m")
    active, suppressed = apply_baseline(
        [f1, f2], [{"rule": "r", "loc": "a", "reason": "known"}])
    assert active == [f2] and suppressed == [f1]
    # an entry without a reason becomes a finding itself
    active, _ = apply_baseline([], [{"rule": "r", "loc": "x"}])
    assert len(active) == 1 and active[0].rule == "baseline"
    # a documented entry matching NO current finding is a stale-
    # suppression WARNING (reported, but does not fail the lint)
    active, _ = apply_baseline(
        [], [{"rule": "r", "loc": "gone", "reason": "fixed long ago"}])
    assert len(active) == 1 and active[0].severity == "warn"
    assert "stale" in active[0].message


def test_shipped_baseline_is_empty():
    entries = engine.load_baseline(engine.DEFAULT_BASELINE)
    assert entries == []


def test_unknown_rule_id_raises():
    with pytest.raises(ValueError, match="unknown rule"):
        engine.run_lint(rule_ids=["no-such-rule"])


def test_fast_lint_clean_on_current_tree():
    """Tier-1 gate: the fast rules (source + artifact lints and the
    collective/purity/dtype proofs on the reduced matrix) hold on the
    tree as committed."""
    report = engine.run_lint(fast=True)
    assert report.errors == []
    assert report.findings == [], "\n".join(map(str, report.findings))
    assert report.clean and report.exit_code == 0
    assert "collective-budget" in report.rules_run


@pytest.mark.slow
def test_full_lint_clean_on_current_tree():
    report = engine.run_lint(fast=False)
    assert report.errors == []
    assert report.findings == [], "\n".join(map(str, report.findings))


def test_report_json_schema_roundtrip(tmp_path):
    report = engine.run_lint(rule_ids=["telemetry-schema"])
    doc = report.to_dict()
    assert doc["schema"] == "pcg-tpu-lint-report/1"
    json.loads(json.dumps(doc))   # json-serializable end to end


def test_cli_exit_codes(tmp_path):
    """`python -m pcg_mpi_solver_tpu.analysis` (jax-light rule subset
    to keep the subprocess cheap): 0 on clean, 2 on unknown rule."""
    out = tmp_path / "report.json"
    ok = subprocess.run(
        [sys.executable, "-m", "pcg_mpi_solver_tpu.analysis",
         "--rules", "telemetry-schema,recovery-paths",
         "--json", str(out)],
        capture_output=True, text=True)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    doc = json.loads(out.read_text())
    assert doc["clean"] is True
    bad = subprocess.run(
        [sys.executable, "-m", "pcg_mpi_solver_tpu.analysis",
         "--rules", "no-such-rule"],
        capture_output=True, text=True)
    assert bad.returncode == 2


def test_analysis_package_import_is_jax_free():
    """The import contract the package __init__ documents (same as the
    repo root package): importing analysis/ must not pull in jax."""
    code = ("import sys; sys.modules.pop('jax', None); "
            "assert 'jax' not in sys.modules; "
            "import pcg_mpi_solver_tpu.analysis; "
            "import pcg_mpi_solver_tpu.analysis.engine; "
            "import pcg_mpi_solver_tpu.analysis.rules_ast; "
            "import pcg_mpi_solver_tpu.analysis.rules_artifacts; "
            "import pcg_mpi_solver_tpu.analysis.rules_config; "
            "import pcg_mpi_solver_tpu.analysis.rules_jaxpr; "
            "assert 'jax' not in sys.modules, 'analysis imported jax'")
    import os

    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)   # keep the package pin from importing jax
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, cwd="/root/repo")
    assert r.returncode == 0, r.stdout + r.stderr
