"""fingerprint-completeness: config reflection vs cache keys & snapshots.

The two worst recent bug classes (ISSUE 7) were trace-affecting config
fields missing from a completeness surface: ``pcg_variant`` absent from
the snapshot ``_fingerprint`` until PR-5 review, ``nrhs``/``rhs_hash``
until PR-6 review.  This rule makes that class MECHANICAL: it reflects
over every ``SolverConfig``/``RunConfig`` field, perturbs it on a real
small solver, and checks that any field that changes the traced step
program (jaxpr text + folded-constant bytes) also changes BOTH

* ``cache/keys.step_cache_key`` — else a warm run could deserialize an
  AOT program compiled for a different config, and
* ``utils/checkpoint._fingerprint`` — else a resume could continue a
  Krylov/time history under different numerics without a mismatch error.

A new config field is forced through classification: bool/int/float
fields get an auto-derived perturbation; string fields need a row in
``STRING_ALTERNATIVES``; fields that cannot be probed must be declared
(with the reason encoded in this module) or the rule fails.  Probe
injection (``key_fn``/``fp_fn``/``fields``) exists so the seeded-
violation tests can prove the rule fires on a deliberately-omitted
field without patching the real cache layer.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional

from pcg_mpi_solver_tpu.analysis.engine import Finding, rule
from pcg_mpi_solver_tpu.config import RunConfig, SolverConfig

#: alternatives for string-typed SolverConfig fields (auto-derivation
#: would be guesswork).  A NEW string field without a row here is an
#: unclassified-field finding — classification is the point.
STRING_ALTERNATIVES = {
    "precision_mode": "mixed",
    "dtype": "float32",
    "dot_dtype": "float32",
    "precond": "block3",
    "pcg_variant": "fused",
    "pallas": "off",
}

#: fields probed on the MIXED base solver (they only reach the traced
#: program through the f32/f64 refinement engine).
MIXED_SCOPE_FIELDS = ("inner_tol", "mixed_plateau_window",
                      "mixed_progress_window", "mixed_progress_ratio",
                      "mixed_progress_min_gain")

#: trace-affecting fields exempt from the SNAPSHOT fingerprint only
#: (they must still key the AOT cache).  Each entry carries its why.
RESUME_NEUTRAL = {
    "donate_carry": (
        "changes only the pjit donation metadata, not the computation — "
        "bit-identical on/off (asserted in tests/test_cache.py), so a "
        "resume across the toggle is safe; it keys the AOT cache via the "
        "explicit donate= component"),
}

#: RunConfig fields that never shape the traced step program: paths,
#: host-side policies, dispatch cadence.  ``solver`` is the SolverConfig
#: (swept field-by-field above); ``time_history`` carries runtime
#: schedule values that enter the program as ARGUMENTS (delta) and are
#: independently fingerprinted for resume-counter integrity
#: (checkpoint._fingerprint deltas/export/plot entries).  A NEW RunConfig
#: field must either join this set (with thought) or be handled like a
#: solver knob — unclassified fields fail the rule.
TRACE_NEUTRAL_RUNCONFIG = frozenset({
    "scratch_path", "model_name", "run_id", "n_parts",
    "partition_method", "speed_test", "checkpoint_every",
    "snapshot_every", "preflight", "cache_dir", "telemetry_path",
    "flight_path", "profile_dir", "comm_probe_iters",
    "solver", "time_history",
    # ISSUE 14: sharded setup builds the SAME partition rows this
    # process would otherwise slice out of a full build — device data,
    # traced programs and cache keys are unchanged (bit-identity
    # asserted in tests/test_setup_shard.py)
    "setup_shard",
})


def _auto_alternative(value):
    """Perturbation for bool/int/float values; None if underivable."""
    if isinstance(value, bool):
        return not value
    if isinstance(value, int):
        return value + 7
    if isinstance(value, float):
        return value * 3.0 if value else 0.5
    return None


def perturbation_for(field: dataclasses.Field, value):
    if field.name in STRING_ALTERNATIVES:
        alt = STRING_ALTERNATIVES[field.name]
        return alt if alt != value else None
    return _auto_alternative(value)


def _default_key_fn():
    from pcg_mpi_solver_tpu.cache.keys import step_cache_key

    return step_cache_key


def _default_fp_fn():
    from pcg_mpi_solver_tpu.utils.checkpoint import _fingerprint

    return _fingerprint


def _key_digest(scfg: SolverConfig, key_fn) -> str:
    """The AOT step key exactly as the driver assembles it, with the
    non-config components held fixed so only the config can move it."""
    return key_fn(
        abstract="<sig>", mesh=[["parts", 2], "cpu"], backend="general",
        solver=dataclasses.asdict(scfg),
        pcg_variant=scfg.pcg_variant,
        precond=getattr(scfg, "precond", "jacobi"),
        nrhs=int(getattr(scfg, "nrhs", 1)),
        trace_len=0, glob_n_dof_eff=100,
        donate=bool(scfg.donate_carry),
        jax_version="<held>", extra={})


def check_structural_key_components(key_fn=None) -> List[Finding]:
    """The documented STRUCTURAL key components must move the digest on
    their own (they exist so the key survives a solver-dict/signature
    serialization refactor): pcg_variant, precond, nrhs, trace_len,
    donate."""
    key_fn = key_fn or _default_key_fn()

    def k(**over):
        kw = dict(abstract="a", mesh="m", backend="b", solver={},
                  trace_len=0, glob_n_dof_eff=1, donate=True,
                  jax_version="j", pcg_variant="classic",
                  precond="jacobi", nrhs=1)
        kw.update(over)
        return key_fn(**kw)

    base = k()
    out = []
    for name, over in (("pcg_variant", {"pcg_variant": "fused"}),
                       ("precond", {"precond": "mg"}),
                       ("nrhs", {"nrhs": 8}),
                       ("trace_len", {"trace_len": 16}),
                       ("donate", {"donate": False})):
        if k(**over) == base:
            out.append(Finding(
                rule="fingerprint-completeness",
                loc=f"field:step_cache_key.{name}",
                message=f"structural key component {name!r} does not "
                        "change the AOT step cache key — programs of "
                        "different shape would collide in the cache"))
    return out


def check_fingerprint_completeness(fields: Optional[List[str]] = None,
                                   key_fn: Optional[Callable] = None,
                                   fp_fn: Optional[Callable] = None,
                                   ) -> List[Finding]:
    """The perturbation sweep (see module docstring).  ``fields``
    restricts to named SolverConfig fields (test hook); ``key_fn`` /
    ``fp_fn`` override the probed surfaces (seeded-violation tests)."""
    from pcg_mpi_solver_tpu.analysis import programs as ap

    key_fn = key_fn or _default_key_fn()
    fp_fn = fp_fn or _default_fp_fn()
    out: List[Finding] = []

    bases = {}

    def base(mode: str):
        if mode not in bases:
            s = (ap.build_solver("general", precision_mode="mixed")
                 if mode == "mixed" else ap.build_solver("general"))
            bases[mode] = (s, ap.program_signature(s), fp_fn(s))
        return bases[mode]

    for f in dataclasses.fields(SolverConfig):
        if fields is not None and f.name not in fields:
            continue
        loc = f"field:SolverConfig.{f.name}"
        mode = "mixed" if f.name in MIXED_SCOPE_FIELDS else "direct"
        base_s, base_sig, base_fp = base(mode)
        value = getattr(base_s.config.solver, f.name)
        alt = perturbation_for(f, value)
        if alt is None:
            out.append(Finding(
                rule="fingerprint-completeness", loc=loc,
                message=f"no perturbation known for SolverConfig."
                        f"{f.name} (= {value!r}): add a "
                        "STRING_ALTERNATIVES row (or make it auto-"
                        "derivable) so new config knobs stay provably "
                        "keyed"))
            continue
        over = {f.name: alt}
        if mode == "mixed":
            over["precision_mode"] = "mixed"
        pert = ap.build_solver("general", **over)
        if ap.program_signature(pert) == base_sig:
            continue   # not trace-affecting: no coverage obligation
        scfg_b = base_s.config.solver
        scfg_p = pert.config.solver
        if _key_digest(scfg_b, key_fn) == _key_digest(scfg_p, key_fn):
            out.append(Finding(
                rule="fingerprint-completeness", loc=loc,
                message=f"SolverConfig.{f.name} changes the traced step "
                        f"program ({value!r} -> {alt!r}) but NOT "
                        "cache/keys.step_cache_key: a warm run could "
                        "deserialize an AOT program compiled for a "
                        "different config"))
        if fp_fn(pert) == base_fp:
            if f.name in RESUME_NEUTRAL:
                pass   # documented exemption (see RESUME_NEUTRAL)
            else:
                out.append(Finding(
                    rule="fingerprint-completeness", loc=loc,
                    message=f"SolverConfig.{f.name} changes the traced "
                            f"step program ({value!r} -> {alt!r}) but "
                            "NOT the snapshot _fingerprint "
                            "(utils/checkpoint.py): a resume would "
                            "continue under different numerics without "
                            "a mismatch error — the PR-5/PR-6 bug class"))
    return out


def check_runconfig_classified() -> List[Finding]:
    """Every RunConfig field must be classified: either declared
    trace-neutral (TRACE_NEUTRAL_RUNCONFIG, with thought) or handled
    like a solver knob.  A new field added without classification is a
    finding — the mechanical forcing function."""
    out = []
    for f in dataclasses.fields(RunConfig):
        if f.name not in TRACE_NEUTRAL_RUNCONFIG:
            out.append(Finding(
                rule="fingerprint-completeness",
                loc=f"field:RunConfig.{f.name}",
                message=f"RunConfig.{f.name} is unclassified: add it to "
                        "TRACE_NEUTRAL_RUNCONFIG (with thought) or wire "
                        "it through the sweep like a solver knob"))
    return out


# ----------------------------------------------------------------------
# cost-model-completeness (ISSUE 12): the analytic per-iteration cost
# model (obs/perf.py) must cover EVERY canonical combination — and stay
# loud about ones it does not know.
# ----------------------------------------------------------------------

#: the synthetic geometry the completeness sweep models: multi-part
#: (so collective terms engage) with a plausible iface payload.
_COST_MODEL_PROBE_SHAPE = dict(n_dof=30_000, n_parts=8, n_iface=2_000,
                               elem_groups=((24, 9_000),),
                               mg_coarse_dofs=4_000)


def check_cost_model_completeness(variants=None, preconds=None,
                                  model_fn=None, nrhs_set=(1, 8),
                                  ) -> List[Finding]:
    """Every ``config.PCG_VARIANTS`` x ``config.PRECONDS`` x nrhs
    combination must produce a finite positive prediction with all four
    attribution phases, and an UNKNOWN variant/precond must raise
    ``KeyError`` (the single-source-table loudness contract) — a combo
    the model silently defaults for would stamp fabricated
    ``predicted_ms_per_iter`` numbers on bench lines.  ``variants`` /
    ``preconds`` / ``model_fn`` are seeded-violation test hooks."""
    from pcg_mpi_solver_tpu import config as _cfg
    from pcg_mpi_solver_tpu.obs import perf as _perf

    shape = _perf.ProblemShape(**_COST_MODEL_PROBE_SHAPE)
    variants = tuple(variants if variants is not None
                     else _cfg.PCG_VARIANTS)
    preconds = tuple(preconds if preconds is not None else _cfg.PRECONDS)
    if model_fn is None:
        def model_fn(v, p, r):
            return _perf.cost_model(shape, v, p, r)
    out: List[Finding] = []
    for v in variants:
        for p in preconds:
            for r in nrhs_set:
                loc = f"combo:{v}/{p}/nrhs{r}"
                try:
                    cm = model_fn(v, p, r)
                except Exception as e:                  # noqa: BLE001
                    out.append(Finding(
                        rule="cost-model-completeness", loc=loc,
                        message=f"cost model has no entry for "
                                f"(pcg_variant={v!r}, precond={p!r}, "
                                f"nrhs={r}): {type(e).__name__}: {e} — "
                                "every canonical combination must "
                                "predict, or bench/telemetry lines for "
                                "it carry no model verdict"))
                    continue
                phases = (cm or {}).get("phases", {})
                missing = [ph for ph in _perf.PHASES if ph not in phases]
                pred = (cm or {}).get("predicted_ms_per_iter", 0)
                if missing or not (isinstance(pred, (int, float))
                                   and pred > 0):
                    out.append(Finding(
                        rule="cost-model-completeness", loc=loc,
                        message=f"cost model entry for ({v}, {p}, "
                                f"nrhs={r}) is degenerate: "
                                f"missing phases {missing}, "
                                f"predicted_ms_per_iter={pred!r} — a "
                                "zero/partial prediction reads as 'free' "
                                "on the measured-vs-model table"))
    # loudness probes: an unknown name must KeyError, never default
    for probe_kw, loc in ((("__no_such_variant__", preconds[0]),
                           "probe:unknown-variant"),
                          ((variants[0], "__no_such_precond__"),
                           "probe:unknown-precond")):
        try:
            model_fn(probe_kw[0], probe_kw[1], 1)
        except KeyError:
            continue
        except Exception as e:                          # noqa: BLE001
            out.append(Finding(
                rule="cost-model-completeness", loc=loc,
                message=f"unknown name raised {type(e).__name__} "
                        "instead of KeyError — consumers catch KeyError "
                        "as the 'table out of sync' signal and must not "
                        "confuse it with an internal failure"))
            continue
        out.append(Finding(
            rule="cost-model-completeness", loc=loc,
            message="cost model silently accepted an unknown "
                    f"{'variant' if 'variant' in loc else 'precond'} "
                    "name — an out-of-sync name table would stamp "
                    "fabricated predictions instead of failing loudly"))
    return out


# ----------------------------------------------------------------------
# partition-key-components (ISSUE 14): the shard-addressed partition
# cache's structural key components must each move the digest alone.
# ----------------------------------------------------------------------

def check_partition_key_components(shard_key_fn=None,
                                   glue_key_fn=None) -> List[Finding]:
    """Every structural component of the shard-addressed partition keys
    (cache/keys.py) must bite on its own — above all ``part_idx``: two
    parts of one partition colliding on one entry would hand a process
    another process's rows on warm start.  The glue key must differ
    from every part key (distinct ``kind``), and an out-of-range
    part_idx must KeyError (a key for a part that cannot exist would
    cache an unreachable entry).  ``shard_key_fn``/``glue_key_fn`` are
    seeded-violation test hooks."""
    from pcg_mpi_solver_tpu.cache import keys as ckeys

    shard_key_fn = shard_key_fn or ckeys.partition_shard_key
    glue_key_fn = glue_key_fn or ckeys.partition_glue_key

    def k(**over):
        kw = dict(n_parts=8, part_idx=0, backend="general",
                  dtype="float64", method="rcb", elem_part_hash=None,
                  pad_multiple=8, extra={})
        kw.update(over)
        return shard_key_fn("<model_fp>", **kw)

    base = k()
    out: List[Finding] = []
    for name, over in (("part_idx", {"part_idx": 3}),
                       ("n_parts", {"n_parts": 4, "part_idx": 0}),
                       ("backend", {"backend": "structured"}),
                       ("dtype", {"dtype": "float32"}),
                       ("method", {"method": "slab2"}),
                       ("elem_part_hash", {"elem_part_hash": "abc"}),
                       ("pad_multiple", {"pad_multiple": 16}),
                       ("extra", {"extra": {"slab2_slabs": 4}})):
        if k(**over) == base:
            out.append(Finding(
                rule="partition-key-components",
                loc=f"field:partition_shard_key.{name}",
                message=f"structural component {name!r} does not change "
                        "the partition shard key — entries of different "
                        "shape would collide; a warm start could hand a "
                        "process another shard's rows"))
    glue = glue_key_fn("<model_fp>", n_parts=8, backend="general",
                       dtype="float64", method="rcb")
    if glue == base:
        out.append(Finding(
            rule="partition-key-components",
            loc="field:partition_glue_key.kind",
            message="the glue key collides with a part entry key — the "
                    "glue must carry its own structural kind"))
    try:
        k(part_idx=99)
    except KeyError:
        pass
    else:
        out.append(Finding(
            rule="partition-key-components",
            loc="probe:part_idx-range",
            message="partition_shard_key accepted part_idx outside "
                    "[0, n_parts) — a key for a part that cannot exist "
                    "caches an unreachable entry instead of failing "
                    "loudly"))
    return out


@rule("partition-key-components", kind="config", fast=True,
      doc="every structural component of the shard-addressed partition "
          "cache keys (part_idx/n_parts/backend/dtype/method/"
          "elem_part_hash/pad_multiple/extra) moves the digest alone, "
          "the glue key is kind-distinct, and out-of-range part_idx "
          "raises KeyError")
def partition_key_components_rule(ctx) -> List[Finding]:
    return check_partition_key_components()


@rule("cost-model-completeness", kind="config", fast=True,
      doc="the analytic per-iteration cost model (obs/perf.py) covers "
          "every config.PCG_VARIANTS x config.PRECONDS x nrhs "
          "combination with a positive all-phase prediction, and "
          "unknown names raise KeyError (never a silent default row)")
def cost_model_completeness_rule(ctx) -> List[Finding]:
    return check_cost_model_completeness()


@rule("fingerprint-completeness", kind="config", fast=False,
      doc="every trace-affecting SolverConfig/RunConfig field appears in "
          "both cache/keys.step_cache_key and the snapshot _fingerprint "
          "(perturb-and-retrace proof; new fields must classify)")
def fingerprint_completeness_rule(ctx) -> List[Finding]:
    return (check_structural_key_components()
            + check_runconfig_classified()
            + check_fingerprint_completeness())
