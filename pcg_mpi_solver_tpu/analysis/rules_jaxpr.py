"""Jaxpr-level rules over the canonical traced-program matrix.

Each rule's checker is exposed as a ``check_*`` function taking one
:class:`~pcg_mpi_solver_tpu.analysis.programs.Program` (or donation
surface), so the seeded-violation tests can feed deliberately-bad
synthetic programs through EXACTLY the code the registered rule runs.

This module stays import-light: jax (via analysis.programs) loads only
when a rule executes.
"""

from __future__ import annotations

from typing import List

from pcg_mpi_solver_tpu.analysis.engine import Finding, rule


# ---------------------------------------------------------------------------
# collective-budget: the loop body runs EXACTLY the declared collectives
# ---------------------------------------------------------------------------

def check_collective_budget(prog) -> List[Finding]:
    """The traced while-body collective histogram must EQUAL the budget
    the ops declared (Ops.body_collective_budget — the same table the
    comm.* telemetry gauges advertise).  Exactly one collective-bearing
    loop body per canonical program; extra primitives, extra counts AND
    under-counts all fail (an under-count means the declaration is stale
    — the gauges would be advertising collectives that do not exist)."""
    from pcg_mpi_solver_tpu.analysis import jaxpr_utils as ju

    hists = [h for h in ju.body_collective_histograms(prog.jaxpr) if h]
    loc = f"program:{prog.name}"
    if len(hists) != 1:
        return [Finding(
            rule="collective-budget", loc=loc,
            message=f"expected exactly one collective-bearing while body,"
                    f" found {len(hists)} (histograms: {hists}) — the "
                    "canonical program shape changed; re-derive the "
                    "budget declarations")]
    got = hists[0]
    want = {k: v for k, v in prog.collective_budget.items() if v}
    if got != want:
        return [Finding(
            rule="collective-budget", loc=loc,
            message=f"loop-body collectives {got} != declared budget "
                    f"{want} (Ops.body_collective_budget / comm.* "
                    "gauges): a re-serialized reduction or an undeclared "
                    "collective is in the hot body")]
    return []


@rule("collective-budget", kind="jaxpr", fast=True,
      doc="traced PCG loop-body psum/ppermute counts equal the budgets "
          "declared next to Ops.comm_estimate, for every variant x nrhs "
          "x backend program")
def collective_budget_rule(ctx) -> List[Finding]:
    out = []
    for prog in ctx.programs():
        out.extend(check_collective_budget(prog))
    return out


# ---------------------------------------------------------------------------
# psum-overlap: the pipelined body's reduction really is overlappable
# ---------------------------------------------------------------------------

def check_psum_overlap(prog) -> List[Finding]:
    """The latency-hiding claim of ``pcg_variant="pipelined"``
    (ISSUE 11), proven chipless: in the traced while-loop body, the
    variant's single fused scalar reduction must be data-INDEPENDENT of
    every other collective — it neither transitively consumes the
    stencil matvec's interface psum / halo ppermute outputs (the fused
    variant's serialization: mu = <z, A.z> reads the matvec) nor feeds
    them (the classic variant's serialization: beta -> p -> matvec) —
    so the lowered program's scheduler is free to run the reduction
    concurrently with the stencil.  XLA lowering never ADDS a data
    dependence, so jaxpr-level independence holds for the compiled
    executable (the runtime twin is the PR-1 profiler span overlap on
    hardware).

    Classic and fused programs are the rule's NEGATIVE CONTROLS: every
    collective in their bodies is serialized against at least one
    other, so a walker that lost dependency edges (and would vacuously
    "prove" overlap) fails loudly here first."""
    from pcg_mpi_solver_tpu.analysis import jaxpr_utils as ju

    loc = f"program:{prog.name}"
    bodies = [ju.while_body(e) for e in ju.while_eqns(prog.jaxpr.jaxpr)]
    bodies = [b for b in bodies if ju.collective_histogram(b)]
    if len(bodies) != 1:
        return [Finding(
            rule="psum-overlap", loc=loc,
            message=f"expected exactly one collective-bearing while "
                    f"body, found {len(bodies)} — the canonical program "
                    "shape changed; re-derive the overlap contract")]
    indep = [r for r in ju.independent_collectives(bodies[0])
             if r["primitive"] == "psum"]
    if prog.variant == "pipelined":
        if len(indep) != 1:
            got = [(r["primitive"], r["out_size"])
                   for r in ju.collective_dependencies(bodies[0])]
            return [Finding(
                rule="psum-overlap", loc=loc,
                message=f"the pipelined body must carry exactly ONE "
                        f"fully data-independent psum (its fused scalar "
                        f"reduction, overlappable with the stencil "
                        f"matvec); found {len(indep)} — the reduction "
                        "got serialized against another collective and "
                        "the latency-hiding claim no longer holds "
                        f"(body collectives: {got})")]
        # the independent psum must be the small stacked scalar
        # reduction (6 reduced scalars x nrhs), not a stencil payload
        # that accidentally lost its consumers
        limit = 16 * max(int(prog.nrhs), 1)
        if indep[0]["out_size"] > limit:
            return [Finding(
                rule="psum-overlap", loc=loc,
                message=f"the body's independent psum has payload size "
                        f"{indep[0]['out_size']} (> {limit}): that is a "
                        "vector collective, not the pipelined scalar "
                        "reduction — the dependency structure changed")]
    elif indep:
        return [Finding(
            rule="psum-overlap", loc=loc,
            message=f"{len(indep)} fully data-independent psum(s) in a "
                    f"{prog.variant} body — every classic/fused "
                    "collective is serialized against the stencil by "
                    "construction, so this means the dependency walker "
                    "lost edges (and the pipelined overlap proof would "
                    "be vacuous)")]
    return []


@rule("psum-overlap", kind="jaxpr", fast=False,
      doc="the pipelined variant's single fused psum is data-independent "
          "of the stencil matvec in BOTH directions in the traced loop "
          "body (latency-hiding proven chipless); classic/fused bodies "
          "prove fully serialized, as negative controls")
def psum_overlap_rule(ctx) -> List[Finding]:
    out = []
    for prog in ctx.programs():
        out.extend(check_psum_overlap(prog))
    return out


# ---------------------------------------------------------------------------
# scope-labels: the trace-attribution named scopes exist in every hot loop
# ---------------------------------------------------------------------------

def check_scope_labels(prog, phase_scopes=None) -> List[Finding]:
    """Every phase label ``obs/profview.py`` buckets trace events on
    (PHASE_SCOPES: pcg/matvec, pcg/precond, pcg/reduce, pcg/axpy) must
    appear in the traced program of EVERY variant, scalar AND blocked —
    a loop body that lost its ``jax.named_scope`` would silently move
    its device-op time into the report's 'other' bucket and the
    hardware attribution table would stop explaining the iteration.
    ``phase_scopes`` is the seeded-violation test hook."""
    from pcg_mpi_solver_tpu.analysis import jaxpr_utils as ju
    from pcg_mpi_solver_tpu.obs.profview import PHASE_SCOPES

    scopes = phase_scopes if phase_scopes is not None else PHASE_SCOPES
    found = ju.scope_labels(prog.jaxpr)
    out = []
    for label in scopes:
        if not found.get(label):
            out.append(Finding(
                rule="scope-labels", loc=f"program:{prog.name}",
                message=f"named-scope label {label!r} is absent from "
                        "the traced program: its phase's device-op "
                        "time would bucket as 'other' in every parsed "
                        "trace (obs/profview.py) — re-thread "
                        "jax.named_scope through the loop body "
                        f"(labels found: {sorted(found)})"))
    return out


def check_matvec_stages(name: str, closed_jaxpr,
                        stages=None) -> List[Finding]:
    """Every ``mv/*`` stage label (ops/matvec.MATVEC_STAGES: gather,
    contract, scatter, halo) must appear in a backend's traced matvec —
    a stage that lost its ``jax.named_scope`` would silently fold its
    device time into the matvec's unstaged rest in every parsed trace.
    ``stages`` is the seeded-violation test hook."""
    from pcg_mpi_solver_tpu.analysis import jaxpr_utils as ju
    from pcg_mpi_solver_tpu.ops.matvec import MATVEC_STAGES

    found = ju.scope_labels(closed_jaxpr, prefix="mv/")
    return [Finding(
        rule="scope-labels", loc=f"program:{name}",
        message=f"matvec stage label {label!r} is absent from the traced "
                "program: its device time would read as unstaged matvec "
                f"time (labels found: {sorted(found)})")
        for label in (stages if stages is not None else MATVEC_STAGES)
        if not found.get(label)]


def check_unknown_label_loudness(bucket_fn=None) -> List[Finding]:
    """The parser-side half of the contract: a device op matching NO
    phase must be COUNTED (other_events/other_ms), and a ``pcg/<x>``
    label outside the known four must land in ``unknown_scopes`` on
    BOTH arrival paths — TPU event-text metadata AND the CPU sidecar
    scope map — never silently dropped.  Probed on synthetic events
    through the REAL bucketing code (``bucket_fn`` is the
    seeded-violation hook)."""
    from pcg_mpi_solver_tpu.obs import profview

    fn = bucket_fn if bucket_fn is not None else profview.bucket_phases
    ops = [
        {"name": "mystery_fusion.9", "base": "mystery_fusion",
         "ts": 0.0, "dur": 5.0, "pid": 1, "tid": 1, "text": ""},
        {"name": "dot.1", "base": "dot", "ts": 10.0, "dur": 7.0,
         "pid": 1, "tid": 1, "text": "jit(f)/pcg/notaphase/dot_general"},
        # the CPU flavor: a bare instruction name whose ONLY route to a
        # label is the compiled-HLO sidecar map
        {"name": "ghost.1", "base": "ghost", "ts": 20.0, "dur": 3.0,
         "pid": 1, "tid": 1, "text": ""},
    ]
    smap = profview.scope_map_from_hlo_text(
        '%ghost.1 = f32[2]{0} add(...), '
        'metadata={op_name="jit(f)/pcg/ghostphase/add"}')
    out = []
    try:
        b = fn(list(ops), smap)
    except Exception as e:                              # noqa: BLE001
        return [Finding(
            rule="scope-labels", loc="probe:unknown-label",
            message=f"bucket_phases crashed on an unbucketable event "
                    f"({type(e).__name__}: {e}) — the tolerant-parse "
                    "contract demands counting, not crashing")]
    total_bucketed = sum(d["us"] for d in b["phases"].values()) \
        + b["other_us"]
    if b["other_events"] < 1 or total_bucketed < 15.0 - 1e-9:
        out.append(Finding(
            rule="scope-labels", loc="probe:unknown-label",
            message=f"bucket_phases DROPPED unbucketable device-op "
                    f"time (other_events={b['other_events']}, "
                    f"bucketed {total_bucketed} of 15.0 us): time that "
                    "matches no phase must be counted and reported, "
                    "never vanish from the attribution table"))
    if (b["unknown_scopes"].get("notaphase", 0) != 1
            or b["unknown_scopes"].get("ghostphase", 0) != 1):
        out.append(Finding(
            rule="scope-labels", loc="probe:unknown-label",
            message="a pcg/<x> label outside the known phase set was "
                    f"not counted into unknown_scopes (got "
                    f"{b['unknown_scopes']}; expected notaphase=1 via "
                    "event text AND ghostphase=1 via the sidecar scope "
                    "map) — a future phase label would silently "
                    "disappear from parsed traces instead of being "
                    "reported as unknown"))
    return out


@rule("scope-labels", kind="jaxpr", fast=True,
      doc="every pcg/* named-scope label the trace consumer "
          "(obs/profview.py) buckets on appears in the traced hot loop "
          "of every variant (scalar + blocked), every backend's matvec "
          "carries the four mv/* stage labels, and the parser counts "
          "+ reports unknown labels instead of dropping them")
def scope_labels_rule(ctx) -> List[Finding]:
    from pcg_mpi_solver_tpu.analysis.programs import matvec_programs

    out = []
    for prog in ctx.programs():
        out.extend(check_scope_labels(prog))
        out.extend(check_matvec_stages(prog.name, prog.jaxpr))
    for name, jx in matvec_programs():
        out.extend(check_matvec_stages(name, jx))
    out.extend(check_unknown_label_loudness())
    return out


# ---------------------------------------------------------------------------
# hot-loop-purity: no host callbacks, no oversized folded constants
# ---------------------------------------------------------------------------

def check_hot_loop_purity(prog, threshold_elems=None) -> List[Finding]:
    from pcg_mpi_solver_tpu.analysis import jaxpr_utils as ju
    from pcg_mpi_solver_tpu.analysis.programs import (
        CALLBACK_PRIMITIVES, LOOP_CONST_THRESHOLD_ELEMS)

    if threshold_elems is None:
        threshold_elems = LOOP_CONST_THRESHOLD_ELEMS
    loc = f"program:{prog.name}"
    out = []
    hits = ju.loop_body_primitives(prog.jaxpr, CALLBACK_PRIMITIVES)
    if hits:
        out.append(Finding(
            rule="hot-loop-purity", loc=loc,
            message=f"callback primitive(s) {hits} inside a while-loop "
                    "body: every Krylov iteration would round-trip to "
                    "the host"))
    for c in ju.oversized_loop_consts(prog.jaxpr, threshold_elems):
        out.append(Finding(
            rule="hot-loop-purity", loc=loc,
            message=f"folded constant {c['dtype']}{list(c['shape'])} "
                    f"({c['size']} elems > {threshold_elems}) feeds the "
                    "while loop: a trace-time-captured operand array "
                    "bloats every AOT export (pass it as a program "
                    "argument instead)"))
    return out


@rule("hot-loop-purity", kind="jaxpr", fast=True,
      doc="no pure_callback/io_callback/debug_callback primitives and no "
          "folded constants above the size threshold inside any traced "
          "while-loop body")
def hot_loop_purity_rule(ctx) -> List[Finding]:
    out = []
    for prog in ctx.programs():
        out.extend(check_hot_loop_purity(prog))
    return out


# ---------------------------------------------------------------------------
# dtype-discipline: f32 programs stay f32
# ---------------------------------------------------------------------------

def check_dtype_discipline(prog) -> List[Finding]:
    """No f64 avals anywhere in an f32-role program (weak-typed scalar
    literals exempt — see jaxpr_utils.dtype_violations).  The mixed
    escalation engine's explicitly-f64 refinement programs are role
    'f64' and out of scope by construction."""
    from pcg_mpi_solver_tpu.analysis import jaxpr_utils as ju

    if prog.role != "f32":
        return []
    leaks = ju.dtype_violations(prog.jaxpr, "float64")
    if not leaks:
        return []
    prims = sorted({d["primitive"] for d in leaks})
    sample = leaks[0]
    return [Finding(
        rule="dtype-discipline", loc=f"program:{prog.name}",
        message=f"{len(leaks)} float64 operand(s)/result(s) in an f32 "
                f"step program (primitives {prims}; e.g. "
                f"{sample['primitive']} on {sample['aval']}): an f64 "
                "leak silently halves MXU throughput and doubles psum "
                "payloads")]


@rule("dtype-discipline", kind="jaxpr", fast=True,
      doc="no f64 avals leak into the all-f32 step programs (weak scalar "
          "literals exempt; the escalation engine's f64 programs are out "
          "of scope)")
def dtype_discipline_rule(ctx) -> List[Finding]:
    out = []
    for prog in ctx.programs():
        out.extend(check_dtype_discipline(prog))
    return out


# ---------------------------------------------------------------------------
# donation-integrity: donate_carry surfaces really alias
# ---------------------------------------------------------------------------

@rule("donation-integrity", kind="jaxpr", fast=False,
      doc="every donate_carry dispatch surface produces input/output "
          "buffer aliasing in the lowered+compiled executable (jax drops "
          "unusable donations SILENTLY — the copy shows up only as HBM "
          "and latency)")
def donation_integrity_rule(ctx) -> List[Finding]:
    from pcg_mpi_solver_tpu.analysis import programs as ap

    out = []
    for surface in ap.donation_surfaces():
        for err in ap.check_donation(surface):
            out.append(Finding(rule="donation-integrity",
                               loc=f"surface:{surface.name}",
                               message=err))
    return out
