"""Command-line interface: the reference's five entry-point programs
(read_input_model / run_metis / partition_mesh / pcg_solver / export_vtk,
orchestrated by examples/run_basic_script.bash) as one typed CLI.

    pcg-tpu ingest    <archive.zip> <scratch>          # unpack MDF bundle
    pcg-tpu partition <scratch> <n_parts>              # element->part map
    pcg-tpu validate  <scratch> [--preflight=]         # preflight checks only
    pcg-tpu solve     <scratch> <run_id> [options]     # SPMD PCG solve
    pcg-tpu solve-many <scratch> <run_id> [options]    # batched multi-RHS solve
    pcg-tpu dynamics  <scratch> <run_id> [options]     # explicit time history
    pcg-tpu newmark   <scratch> <run_id> [options]     # implicit time history
    pcg-tpu export    <scratch> <run_id> <vars> <mode> # frames -> .vtu
    pcg-tpu demo      [--nx ...]                       # synthetic end-to-end
    pcg-tpu bench                                      # benchmark harness
    pcg-tpu warmup    <scratch> [options]              # pre-bake caches
    pcg-tpu cache-stats [--cache-dir D]                # warm-path cache table
    pcg-tpu lint      [--fast] [--json F]              # contract lint (analysis/)
    pcg-tpu perf-report [--nx N | scratch]             # measured-vs-model phases
    pcg-tpu prof-report <trace-artifact>               # parse a captured device trace
    pcg-tpu fleet-report <capture-root>                # cross-process collective skew
    pcg-tpu trend     [BENCH_r*.json ...]              # bench-trend regression sentinel
    pcg-tpu summary   <run.jsonl> [...]                # offline telemetry summary
    pcg-tpu watch     <run.jsonl> [--once]             # live monitor + stall alarm
    pcg-tpu telemetry-merge <run.jsonl> --out M.jsonl  # merge per-process shards
    pcg-tpu serve     --spool DIR [model opts]         # multi-tenant solve daemon
    pcg-tpu submit    --spool DIR --scale S            # drop a job into the spool
    pcg-tpu jobs      --spool DIR                      # job table from the journal

Settings come from ``--settings settings.json`` (same shape as the
reference's GlobSettings: TimeHistoryParam/SolverParam,
run_basic_script.bash:30-49) or per-flag overrides.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np


def _load_settings(path, args) -> "RunConfig":
    from pcg_mpi_solver_tpu.config import RunConfig, SolverConfig, TimeHistoryConfig

    th, sp = {}, {}
    if path and os.path.exists(path):
        with open(path) as f:
            raw = json.load(f)
        th = raw.get("TimeHistoryParam", {})
        sp = raw.get("SolverParam", {})
    # default precision is "direct" (f64, reference parity) — a reference-
    # shaped settings file without PrecisionMode must not change numerics;
    # pass --precision mixed (or PrecisionMode) for the TPU performance path.
    solver = SolverConfig(
        tol=float(getattr(args, "tol", None) or sp.get("Tol", 1e-7)),
        max_iter=int(getattr(args, "max_iter", None) or sp.get("MaxIter", 10000)),
        precision_mode=getattr(args, "precision", None) or sp.get("PrecisionMode", "direct"),
        precond=getattr(args, "precond", None) or sp.get("Precond", "jacobi"),
        # classic stays the bit-exact reference-parity default; "fused"
        # opts into the single-reduction Chronopoulos–Gear loop
        pcg_variant=(getattr(args, "pcg_variant", None)
                     or sp.get("PcgVariant", "classic")),
        # dispatch cap override (settings-only; -1 = auto): tests and
        # small chaos drills force the chunked/resumable path below the
        # auto-engage size, where snapshots/recovery actually exist
        iters_per_dispatch=int(sp.get("ItersPerDispatch", -1)),
    )
    time_history = TimeHistoryConfig(
        time_step_delta=th.get("TimeStepDelta", [0.0, 1.0]),
        export_flag=bool(th.get("ExportFlag", True)),
        export_frame_rate=int(th.get("ExportFrmRate", 1)),
        export_frames=th.get("ExportFrms", []),
        plot_flag=bool(th.get("PlotFlag", False)),
        export_vars=th.get("ExportVars", "U"),
    )
    cfg = RunConfig(solver=solver, time_history=time_history)
    _apply_telemetry_flags(cfg, args)
    return cfg


def _apply_telemetry_flags(cfg, args) -> None:
    """Wire the shared per-run flags into the RunConfig: --telemetry-out
    (JSONL sink), --trace-resid (in-graph convergence ring),
    --cache-dir, and the validate/ --preflight policy override."""
    cfg.telemetry_path = getattr(args, "telemetry_out", None) or ""
    cfg.flight_path = getattr(args, "flight_out", None) or ""
    cfg.solver.trace_resid = int(getattr(args, "trace_resid", None) or 0)
    cfg.cache_dir = _resolve_cache_dir(args)
    cfg.preflight = getattr(args, "preflight", None) or ""


def _resolve_cache_dir(args) -> str:
    """One resolution rule for every subcommand (warmup MUST land in the
    same dir the later solve reads, so they cannot have different
    defaults): the --cache-dir flag, else the PCG_TPU_CACHE_DIR env var,
    else off."""
    return getattr(args, "cache_dir", None) or \
        os.environ.get("PCG_TPU_CACHE_DIR", "")


def _resolve_partition_mesh(n_parts_arg, scratch):
    """(n_parts, elem_part, n_dev, n_dev_used): the n_parts default, the
    scratch MeshPart_<n>.npy element->part map, and the device count
    that divides n_parts — ONE resolution shared by solve and warmup,
    because warmup's entire value depends on baking caches for the
    IDENTICAL mesh/partition inputs the later solve resolves."""
    import jax

    n_dev = len(jax.devices())
    n_parts = n_parts_arg or n_dev
    elem_part = None
    if scratch:
        part_file = os.path.join(scratch, "ModelData",
                                 f"MeshPart_{n_parts}.npy")
        if os.path.exists(part_file):
            elem_part = np.load(part_file)
    # use as many devices as divide n_parts
    n_dev_used = n_dev if n_parts % n_dev == 0 else max(
        d for d in range(1, min(n_dev, n_parts) + 1) if n_parts % d == 0)
    return n_parts, elem_part, n_dev, n_dev_used


def _add_cache_flag(p) -> None:
    p.add_argument("--cache-dir", default=None, metavar="DIR",
                   help="warm-path cache directory (cache/): partitions "
                        "are served from a content-addressed on-disk "
                        "cache, the PCG step program is AOT-exported, and "
                        "jax's persistent XLA compilation cache lives "
                        "under DIR/xla — the second solve of the same "
                        "model/n_parts/backend pays near-zero setup "
                        "(pre-bake with `pcg-tpu warmup`; env default: "
                        "PCG_TPU_CACHE_DIR)")


def _finish_telemetry(solver, args) -> None:
    """End-of-run telemetry surfaces: the --summary table and the
    recorder's sink shutdown (flushes/closes the JSONL file)."""
    if getattr(args, "summary", False):
        print(solver.recorder.summary())
    if getattr(args, "telemetry_out", None):
        print(f">telemetry: {args.telemetry_out}")
    solver.recorder.close()


def _precond_choices():
    # derived from the canonical table (config.PRECONDS) like the
    # variant flag below: a precond added to the table must be
    # selectable from every CLI surface without six hand-edits
    from pcg_mpi_solver_tpu.config import PRECONDS

    return list(PRECONDS)


def _add_variant_flag(p) -> None:
    from pcg_mpi_solver_tpu.config import PCG_VARIANTS

    p.add_argument("--pcg-variant", choices=list(PCG_VARIANTS),
                   default=None, dest="pcg_variant",
                   help="PCG loop formulation: classic = MATLAB-"
                        "compatible 3-reduction loop (bit-exact "
                        "reference parity; default), fused = "
                        "Chronopoulos-Gear single-reduction recurrence "
                        "(ONE collective per iteration — cuts the "
                        "between-matvec latency at scale), pipelined = "
                        "Ghysels-Vanroose depth-1 pipelining (the one "
                        "collective overlaps the stencil matvec "
                        "entirely; 4 extra carry vectors, tighter "
                        "drift guard).  Iteration counts of the non-"
                        "classic variants differ by O(1); see "
                        "docs/RUNBOOK.md 'Choosing pcg_variant'")


def _add_preflight_flag(p) -> None:
    p.add_argument("--preflight", choices=["fail", "warn", "off"],
                   default=None,
                   help="model/config preflight gate (validate/): fail "
                        "= reject pathological inputs before any "
                        "partition/compile work (default), warn = "
                        "report and proceed, off = skip the checks "
                        "(env default: PCG_TPU_PREFLIGHT)")


def _add_resilience_flags(p, granularity: str,
                          elastic: bool = False) -> None:
    """--snapshot-every / --max-recoveries / --resume, shared by the
    solve, dynamics and newmark subcommands; ``granularity`` names what
    one snapshot interval means on that path.  ``elastic`` additionally
    exposes --resume-elastic (the quasi-static driver only — the path
    ``Solver.resume_elastic`` serves)."""
    p.add_argument("--snapshot-every", type=int, default=0,
                   help=f"resumable snapshots (resilience/): persist "
                        f"state every N {granularity} so a "
                        "killed/preempted run loses at most N and "
                        "--resume continues where it left off (0 = off; "
                        "on-disk retention: PCG_TPU_SNAP_KEEP, "
                        "default 2)")
    p.add_argument("--max-recoveries", type=int, default=None,
                   help="recovery budget for breakdowns, NaN/Inf "
                        "corruption and device-loss failures (default "
                        "2; 0 = report-and-stop)")
    p.add_argument("--resume", action="store_true",
                   help=f"continue from the latest snapshot/checkpoint "
                        f"of this run ({granularity} granularity)")
    if not elastic:
        return
    p.add_argument("--resume-elastic", default=None, metavar="DIR",
                   nargs="?", const="",
                   help="resume a MULTI-PROCESS run's committed snapshot "
                        "epochs / checkpoints on THIS (typically smaller) "
                        "process count (resilience/distributed, ISSUE "
                        "18): re-joins the group-consistent shards and "
                        "accepts the n_procs fingerprint mismatch as a "
                        "named elastic_resume event.  DIR = the dead "
                        "fleet's checkpoint dir (default: this config's "
                        "checkpoint path)")


def _add_telemetry_flags(p) -> None:
    p.add_argument("--telemetry-out", default=None, metavar="FILE.jsonl",
                   help="append schema-versioned telemetry events (one "
                        "JSON object per line: step metrics, dispatch "
                        "timings, residual traces, run summary) here")
    p.add_argument("--trace-resid", type=int, default=0, metavar="N",
                   help="record the last N per-iteration (normr, rho, "
                        "stag, flag) samples on device and surface them "
                        "once per solve (0 = off; clamped to max_iter)")
    p.add_argument("--flight-out", default=None, metavar="FILE.jsonl",
                   help="crash-durable flight recorder (obs/flight.py): "
                        "fsync-per-event begin/end brackets + heartbeats "
                        "around every solve dispatch, so a device loss "
                        "or SIGKILL mid-solve leaves a parseable artifact "
                        "(read it back with `pcg-tpu summary`; env "
                        "default: PCG_TPU_FLIGHT)")
    p.add_argument("--summary", action="store_true",
                   help="print the per-step / per-dispatch telemetry "
                        "table after the run")


def cmd_ingest(args):
    from pcg_mpi_solver_tpu.models.mdf import ingest_archive, read_mdf

    mdf = ingest_archive(args.archive, args.scratch)
    model = read_mdf(mdf)
    print(f">extracted to {mdf}")
    print(f">elements:  {model.n_elem}")
    print(f">nodes:     {model.n_node}")
    print(f">dofs:      {model.n_dof}")


def cmd_partition(args):
    from pcg_mpi_solver_tpu.models.mdf import read_mdf
    from pcg_mpi_solver_tpu.parallel.partition import make_elem_part

    model = read_mdf(os.path.join(args.scratch, "ModelData", "MDF"))
    print(f">partitioning {model.n_elem} elements into {args.n_parts} parts "
          f"({args.method})..")
    part = make_elem_part(model, args.n_parts, method=args.method)
    out = os.path.join(args.scratch, "ModelData", f"MeshPart_{args.n_parts}.npy")
    np.save(out, part)
    print(f">saved {out}")


def cmd_solve(args):
    from pcg_mpi_solver_tpu.models.mdf import read_mdf
    from pcg_mpi_solver_tpu.solver.driver import Solver
    from pcg_mpi_solver_tpu.utils.io import RunStore

    cfg = _load_settings(args.settings, args)
    cfg.scratch_path = args.scratch
    cfg.run_id = args.run_id
    cfg.speed_test = bool(args.speed_test)
    cfg.checkpoint_every = int(args.checkpoint_every or 0)
    cfg.snapshot_every = int(args.snapshot_every or 0)
    if args.max_recoveries is not None:
        cfg.solver.max_recoveries = int(args.max_recoveries)
    cfg.profile_dir = args.profile_dir or ""
    model = read_mdf(os.path.join(args.scratch, "ModelData", "MDF"))
    cfg.time_history.dt = model.dt   # frame timestamps follow the model's dt
    n_parts, elem_part, n_dev, n_dev_used = _resolve_partition_mesh(
        args.n_parts, args.scratch)

    from pcg_mpi_solver_tpu.parallel.mesh import make_mesh

    print(f">solving on {n_dev_used}/{n_dev} device(s), {n_parts} parts "
          f"({cfg.solver.precision_mode} precision)..")
    s = Solver(model, cfg, mesh=make_mesh(n_dev_used), n_parts=n_parts,
               elem_part=elem_part, backend=args.backend)
    print(f">backend: {s.backend}")
    store = RunStore(cfg.result_path, cfg.model_name)
    out_store = None if cfg.speed_test else store
    if getattr(args, "resume_elastic", None) is not None:
        res = s.resume_elastic(args.resume_elastic or None,
                               store=out_store)
    else:
        res = s.solve(store=out_store, resume=bool(args.resume))
    # With --resume, earlier steps were restored: label only the ones run.
    t_first = len(s.flags) - len(res) + 1
    for t, r in enumerate(res, t_first):
        print(f">step {t}: flag={r.flag} iters={r.iters} relres={r.relres:.3e} "
              f"wall={r.wall_s:.2f}s")
    td = s.time_data()
    print(f">calculation time: {td['Mean_CalcTime']:.2f} sec")
    _finish_telemetry(s, args)
    print(">success!")


def cmd_solve_many(args):
    """Batched multi-RHS solve: a LIST of load cases against one shared
    partitioned operator (Solver.solve_many — the multi-tenant solve
    path).  The block comes from ``--rhs loads.npy`` ((n_dof, nrhs) or
    (nrhs, n_dof)) or ``--scales "1.0,0.5,2.0"`` (columns = scale *
    model reference load F); each column is validated per request
    (validate.check_rhs_block names the offending column) on top of the
    construction-time preflight.  One Krylov loop solves all columns
    lockstep — converged columns freeze, per-iteration collective count
    independent of the block width — and per-RHS flags/relres/iters are
    printed and emitted as `rhs_solve` telemetry events.

    Resilience rides the blocked path for real: --snapshot-every /
    --resume persist and continue the blocked carry mid-solve
    (``many_*.npz``), and --max-recoveries bounds the PER-COLUMN
    recovery ladder — a flag-2/4 breakdown or NaN/Inf poison in one
    column restarts/escalates that column alone while the others keep
    iterating bit-identically; an unrecoverable column is QUARANTINED
    (flag 5 + `rhs_quarantine` telemetry) instead of failing the block
    (docs/RUNBOOK.md "Blocked solve failure modes & quarantine")."""
    from pcg_mpi_solver_tpu.models.mdf import read_mdf
    from pcg_mpi_solver_tpu.solver.driver import Solver, normalize_rhs_block

    cfg = _load_settings(args.settings, args)
    cfg.scratch_path = args.scratch
    cfg.run_id = args.run_id
    cfg.snapshot_every = int(args.snapshot_every or 0)
    if args.max_recoveries is not None:
        cfg.solver.max_recoveries = int(args.max_recoveries)
    model = read_mdf(os.path.join(args.scratch, "ModelData", "MDF"))
    if args.rhs:
        # the ONE shape heuristic lives in normalize_rhs_block (shared
        # with Solver.solve_many) — the CLI only needs the width early
        # for the config/telemetry stamp, so this is the shape-only pass
        # (no dtype: the transpose is a view, no full-block copy;
        # solve_many converts once to the solve dtype)
        fb = normalize_rhs_block(np.load(args.rhs), model.n_dof)
    elif args.scales:
        try:
            scales = [float(v) for v in args.scales.split(",")
                      if v.strip()]
        except ValueError:
            raise SystemExit(f"solve-many: --scales {args.scales!r} is "
                             "not a comma-separated list of numbers")
        if not scales:
            raise SystemExit("solve-many: --scales parsed to zero load "
                             "cases; pass e.g. --scales \"1.0,0.5\"")
        fb = np.stack([np.asarray(model.F) * sc for sc in scales], axis=-1)
    else:
        raise SystemExit("solve-many: pass --rhs FILE.npy (columns = load "
                         "cases) or --scales \"1.0,0.5,...\"")
    cfg.solver.nrhs = fb.shape[1]
    n_parts, elem_part, n_dev, n_dev_used = _resolve_partition_mesh(
        args.n_parts, args.scratch)

    from pcg_mpi_solver_tpu.parallel.mesh import make_mesh

    print(f">solving {fb.shape[1]} load cases on {n_dev_used}/{n_dev} "
          f"device(s), {n_parts} parts "
          f"({cfg.solver.precision_mode} precision, "
          f"{cfg.solver.pcg_variant} variant)..")
    s = Solver(model, cfg, mesh=make_mesh(n_dev_used), n_parts=n_parts,
               elem_part=elem_part, backend=args.backend)
    print(f">backend: {s.backend}  setup: {s.setup_s:.2f}s "
          f"({s.setup_cache} partition)")
    res = s.solve_many(fb, resume=bool(args.resume))
    for j in range(res.nrhs):
        tag = "  [QUARANTINED]" if j in res.quarantined else ""
        print(f">rhs {j}: flag={int(res.flags[j])} "
              f"iters={int(res.iters[j])} relres={res.relres[j]:.3e}{tag}")
    print(f">block wall: {res.wall_s:.2f}s ({res.nrhs} load cases, "
          f"one operator)")
    if res.recoveries:
        print(f">recoveries: {res.recoveries} per-column ladder "
              f"attempt(s) consumed")
    if res.quarantined:
        print(f">quarantined columns: {list(res.quarantined)} — "
              "returned their min-residual iterate (flag 5); see "
              "docs/RUNBOOK.md 'Blocked solve failure modes'")
    out = os.path.join(cfg.result_path, "u_many")
    os.makedirs(cfg.result_path, exist_ok=True)
    np.save(out, s.displacement_global_many(res.x))
    print(f">solutions (n_dof, nrhs) -> {out}.npy")
    _finish_telemetry(s, args)
    print(">success!")


def cmd_serve(args):
    """Run the multi-tenant solve service (serve/, ISSUE 19): one warm
    partitioned operator serving filesystem-submitted jobs exactly once.

    The daemon polls ``--spool``/incoming for specs (``pcg-tpu
    submit``), prices each admission with the analytic cost model
    against the job's deadline, packs compatible jobs into standard
    nrhs blocks and dispatches them through ``Solver.solve_many`` — a
    poisoned tenant's column quarantines alone (PR 8) while co-batched
    tenants finish.  Every lifecycle transition is an fsync'd record in
    ``spool/journal.jsonl``; restarting the daemon over the same spool
    replays the journal (no job lost, none solved twice).  SIGTERM
    drains gracefully; watch the journal live with ``pcg-tpu watch
    spool/journal.jsonl``."""
    from pcg_mpi_solver_tpu.serve.daemon import ServeDaemon
    from pcg_mpi_solver_tpu.solver.driver import Solver

    cfg = _load_settings(args.settings, args)
    if args.synthetic:
        from pcg_mpi_solver_tpu.models.synthetic import make_cube_model

        try:
            dims = [int(v) for v in args.synthetic.split(",")]
        except ValueError:
            raise SystemExit(f"serve: --synthetic {args.synthetic!r} is "
                             "not NX[,NY,NZ]")
        dims += [0] * (3 - len(dims))
        model = make_cube_model(dims[0], dims[1], dims[2], E=30e9,
                                nu=0.2, load="traction", load_value=1e6,
                                heterogeneous=True)
    elif args.scratch:
        from pcg_mpi_solver_tpu.models.mdf import read_mdf

        cfg.scratch_path = args.scratch
        model = read_mdf(os.path.join(args.scratch, "ModelData", "MDF"))
    else:
        raise SystemExit("serve: pass a <scratch> dir or --synthetic NX")
    try:
        widths = sorted({int(v) for v in args.widths.split(",")})
    except ValueError:
        raise SystemExit(f"serve: --widths {args.widths!r} is not a "
                         "comma-separated list of ints")
    n_parts, elem_part, n_dev, n_dev_used = _resolve_partition_mesh(
        args.n_parts, args.scratch)

    from pcg_mpi_solver_tpu.parallel.mesh import make_mesh

    print(f">serve: warming {model.n_dof} dofs on {n_dev_used}/{n_dev} "
          f"device(s), {n_parts} parts..")
    s = Solver(model, cfg, mesh=make_mesh(n_dev_used), n_parts=n_parts,
               elem_part=elem_part, backend=args.backend)
    daemon = ServeDaemon(
        s, args.spool, queue_max=args.queue_max, widths=widths,
        expected_iters=args.expected_iters, poll_s=args.poll_s)
    print(f">serve: spool={args.spool} queue_max={args.queue_max} "
          f"widths={daemon.widths} (SIGTERM drains; journal="
          f"{daemon.journal.path})")
    reason = daemon.run(max_blocks=args.max_blocks,
                        idle_exit_s=args.idle_exit_s)
    print(f">serve: drained ({reason}) — {daemon.jobs_done} done, "
          f"{daemon.jobs_failed} failed, "
          f"{daemon.admission.shed_count} shed, "
          f"{daemon.blocks} block(s)")
    _finish_telemetry(s, args)
    print(">success!")


def cmd_submit(args):
    """Submit one job to a solve-service spool (import-light: works
    from a login node without the accelerator environment).  Prints the
    job id; poll ``spool/results/<job>.json`` — every submitted job
    eventually gets a result with a named verdict."""
    from pcg_mpi_solver_tpu.serve import jobs as sjobs

    spec = {"deadline_s": args.deadline_s}
    if args.job_id:
        spec["job"] = args.job_id
    if args.rhs is not None:
        spec["rhs"] = args.rhs
    if args.scale is not None:
        spec["scale"] = args.scale
    try:
        job = sjobs.submit(args.spool, spec)
    except ValueError as e:
        raise SystemExit(f"submit: {e}")
    print(f">submitted {job} -> "
          f"{sjobs.result_path(args.spool, job)}")


def cmd_jobs(args):
    """Job table of a solve-service spool, folded from the journal —
    works on a live daemon's spool (the journal is append-only and
    torn-tail tolerant) and on a crashed one (what WOULD replay)."""
    from pcg_mpi_solver_tpu.serve import jobs as sjobs
    from pcg_mpi_solver_tpu.serve.journal import read_journal, replay_jobs

    path = sjobs.journal_path(args.spool)
    if not os.path.exists(path):
        raise SystemExit(f"jobs: no journal at {path}")
    events, truncated = read_journal(path)
    states = replay_jobs(events)
    if truncated:
        print(f">warning: {truncated} torn journal line(s) skipped")
    print(f">{'job':12s} {'ordinal':>7s} {'state':12s} verdict")
    for st in sorted(states.values(),
                     key=lambda s: (s["ordinal"] is None,
                                    s["ordinal"] or 0)):
        o = "-" if st["ordinal"] is None else str(st["ordinal"])
        print(f">{st['job']:12s} {o:>7s} {st['op'] or '?':12s} "
              f"{st['verdict'] or ''}")
    n_term = sum(st["terminal"] for st in states.values())
    print(f">{len(states)} job(s), {n_term} terminal, "
          f"{len(states) - n_term} in flight")


def cmd_validate(args):
    """Run the validate/ preflight checks against a scratch model and
    report every one — the dry-run twin of the gate that solve/dynamics/
    newmark apply at construction.  The --preflight policy drives the
    exit code exactly as it would drive the gate: fail (default) exits
    non-zero on any failed check, warn reports and exits zero, off skips
    the scans entirely."""
    from pcg_mpi_solver_tpu.models.mdf import read_mdf
    from pcg_mpi_solver_tpu.validate import preflight_checks, resolve_policy

    pol = resolve_policy(getattr(args, "preflight", None))
    if pol == "off":
        print(">validate: preflight policy is off; nothing checked")
        return
    cfg = _load_settings(args.settings, args)
    model = read_mdf(os.path.join(args.scratch, "ModelData", "MDF"))
    print(f">preflight: {model.n_elem} elems / {model.n_dof} dofs")
    results = preflight_checks(model, cfg, context={"kind": "validate"})
    n_fail = 0
    for r in results:
        tag = {"ok": "  ok ", "warn": " WARN", "fail": " FAIL"}[r.status]
        n_fail += r.status == "fail"
        print(f">[{tag}] {r.name}" + (f": {r.detail}" if r.detail else ""))
    if n_fail and pol == "fail":
        raise SystemExit(f"validate: {n_fail} failed check(s)")
    if n_fail:
        print(f">validate: {n_fail} failed check(s) (policy={pol}; "
              "exit 0)")
    else:
        print(">validate: all checks passed")


def _print_dyn_summary(store_dir, name, u, extra=""):
    os.makedirs(store_dir, exist_ok=True)
    out = os.path.join(store_dir, name)
    np.save(out, u)
    print(f">final displacement -> {out}.npy{extra}")


def cmd_dynamics(args):
    """Explicit central-difference time history (solver/dynamics.py),
    preemption-safe: --snapshot-every N checkpoints the full state every
    N TIMESTEPS, --resume continues mid-history bit-identically."""
    from pcg_mpi_solver_tpu.models.mdf import read_mdf
    from pcg_mpi_solver_tpu.parallel.mesh import make_mesh
    from pcg_mpi_solver_tpu.solver.dynamics import DynamicsSolver

    cfg = _load_settings(args.settings, args)
    cfg.scratch_path = args.scratch
    cfg.run_id = args.run_id
    cfg.snapshot_every = int(args.snapshot_every or 0)
    if args.max_recoveries is not None:
        cfg.solver.max_recoveries = int(args.max_recoveries)
    model = read_mdf(os.path.join(args.scratch, "ModelData", "MDF"))
    n_parts, _elem_part, n_dev, n_dev_used = _resolve_partition_mesh(
        args.n_parts, args.scratch)
    probe = tuple(int(d) for d in (args.probe_dofs or "").split(",") if d)
    print(f">explicit dynamics on {n_dev_used}/{n_dev} device(s), "
          f"{n_parts} parts, {args.n_steps} steps..")
    dyn = DynamicsSolver(model, cfg, mesh=make_mesh(n_dev_used),
                         n_parts=n_parts, dt=args.dt,
                         damping=args.damping, probe_dofs=probe,
                         backend=args.backend)
    print(f">backend: {dyn.backend}  dt={dyn.dt:.4e}")
    res = dyn.run(args.n_steps, export_every=args.export_every,
                  resume=bool(args.resume))
    print(f">integrated {args.n_steps} steps "
          f"({len(res.frames)} frames, {res.probe_u.shape[0]} probes)")
    _print_dyn_summary(cfg.result_path, "u_dynamics", res.u)
    if len(probe):
        np.save(os.path.join(cfg.result_path, "probe_dynamics"),
                res.probe_u)
        print(f">probe series -> {cfg.result_path}/probe_dynamics.npy")
    _finish_telemetry(dyn, args)
    print(">success!")


def cmd_newmark(args):
    """Implicit Newmark-beta time history (solver/newmark.py), one PCG
    solve per step, preemption-safe: --snapshot-every N checkpoints the
    kinematic state every N TIMESTEPS, --resume continues mid-history
    bit-identically (including the per-step trace ring)."""
    from pcg_mpi_solver_tpu.models.mdf import read_mdf
    from pcg_mpi_solver_tpu.parallel.mesh import make_mesh
    from pcg_mpi_solver_tpu.solver.newmark import NewmarkSolver

    cfg = _load_settings(args.settings, args)
    cfg.scratch_path = args.scratch
    cfg.run_id = args.run_id
    cfg.snapshot_every = int(args.snapshot_every or 0)
    if args.max_recoveries is not None:
        cfg.solver.max_recoveries = int(args.max_recoveries)
    model = read_mdf(os.path.join(args.scratch, "ModelData", "MDF"))
    n_parts, _elem_part, n_dev, n_dev_used = _resolve_partition_mesh(
        args.n_parts, args.scratch)
    dt = args.dt if args.dt else (model.dt if model.dt > 0 else 1.0)
    print(f">Newmark dynamics on {n_dev_used}/{n_dev} device(s), "
          f"{n_parts} parts, {args.n_steps} steps, dt={dt:.4e}..")
    s = NewmarkSolver(model, cfg, mesh=make_mesh(n_dev_used),
                      n_parts=n_parts, dt=dt, beta=args.beta,
                      gamma=args.gamma, damping=args.damping,
                      backend=args.backend)
    print(f">backend: {s.backend}")
    res = s.run([1.0] * args.n_steps, resume=bool(args.resume))
    t_first = len(s.flags) - len(res) + 1
    for t, r in enumerate(res, t_first):
        print(f">step {t}: flag={r.flag} iters={r.iters} "
              f"relres={r.relres:.3e} wall={r.wall_s:.2f}s")
    _print_dyn_summary(cfg.result_path, "u_newmark",
                       s.displacement_global())
    _finish_telemetry(s, args)
    print(">success!")


def cmd_export(args):
    from pcg_mpi_solver_tpu.models.mdf import read_mdf
    from pcg_mpi_solver_tpu.utils.io import RunStore
    from pcg_mpi_solver_tpu.vtk.export import export_vtk

    from pcg_mpi_solver_tpu.config import RunConfig

    model = read_mdf(os.path.join(args.scratch, "ModelData", "MDF"))
    cfg = RunConfig(scratch_path=args.scratch, run_id=args.run_id)
    store = RunStore(cfg.result_path, "model")
    files = export_vtk(model, store, args.vars.split(), args.mode)
    print(f">wrote {len(files)} vtu files to {store.vtk_path}")


def cmd_demo(args):
    from pcg_mpi_solver_tpu.models.synthetic import make_cube_model
    from pcg_mpi_solver_tpu.solver.driver import Solver
    from pcg_mpi_solver_tpu.utils.io import RunStore
    from pcg_mpi_solver_tpu.vtk.export import export_vtk

    cfg = _load_settings(args.settings, args)
    cfg.scratch_path = args.scratch
    cfg.time_history.export_vars = "U D ES PS PE"
    vtk_vars, vtk_mode = ["U", "PS1", "PS3", "ES"], "Full"
    if getattr(args, "poisson", False):
        from pcg_mpi_solver_tpu.models.synthetic import make_poisson_model

        cfg.model_name = "demo_poisson"
        cfg.time_history.export_vars = "U"      # scalar class: U only
        vtk_vars, vtk_mode = ["U"], "Boundary"
        model = make_poisson_model(args.nx, args.ny or 0, args.nz or 0,
                                   heterogeneous=True, seed=1)
        print(f">demo poisson: {model.n_elem} elems / {model.n_dof} dofs "
              "(scalar diffusion)")
    elif args.octree:
        from pcg_mpi_solver_tpu.models.octree import make_octree_model

        cfg.model_name = "demo_octree"
        model = make_octree_model(
            args.nx, args.ny or args.nx, args.nz or args.nx,
            max_level=args.max_level, n_incl=3, seed=1,
            E=30e9, nu=0.2, load="traction", load_value=1e6)
        print(f">demo octree: {model.n_elem} elems / {model.n_dof} dofs / "
              f"{len(model.elem_lib)} pattern types")
    else:
        cfg.model_name = "demo_cube"
        model = make_cube_model(args.nx, args.ny or 0, args.nz or 0,
                                E=30e9, nu=0.2, load="traction",
                                load_value=1e6, heterogeneous=True)
        print(f">demo model: {model.n_elem} elems / {model.n_dof} dofs")
    # the octree demo EXPLICITLY showcases the hybrid level-grid path
    # (auto-selection is deprecation-gated behind PCG_TPU_ENABLE_HYBRID,
    # ISSUE 14 — an explicit request stays honored)
    s = Solver(model, cfg, backend="hybrid" if args.octree else "auto")
    store = RunStore(cfg.result_path, cfg.model_name)
    res = s.solve(store=store)
    for t, r in enumerate(res, 1):
        print(f">step {t}: flag={r.flag} iters={r.iters} relres={r.relres:.3e} "
              f"wall={r.wall_s:.2f}s  [{s.backend} backend]")
    files = export_vtk(model, store, vtk_vars, vtk_mode)
    print(f">wrote {len(files)} vtu files to {store.vtk_path}")
    _finish_telemetry(s, args)
    print(">success!")


def cmd_warmup(args):
    """Pre-bake the warm-path caches for a model/config (docs/RUNBOOK.md
    "Warm path"): partition + AOT step + persistent XLA compile entries,
    so the solve inside a scarce hardware window pays no setup."""
    from pcg_mpi_solver_tpu.cache.partition_cache import format_stats
    from pcg_mpi_solver_tpu.parallel.mesh import make_mesh
    from pcg_mpi_solver_tpu.solver.driver import Solver

    cfg = _load_settings(args.settings, args)
    cfg.cache_dir = _resolve_cache_dir(args)
    if not cfg.cache_dir:
        # refusing to invent a default: a warmup baked into a dir the
        # later solve does not read is worse than no warmup at all
        raise SystemExit(
            "warmup: pass --cache-dir DIR (or set PCG_TPU_CACHE_DIR) — "
            "and run the solve with the SAME dir to use the baked caches")
    if args.demo_nx:
        from pcg_mpi_solver_tpu.models.synthetic import make_cube_model

        model = make_cube_model(args.demo_nx, 0, 0, E=30e9, nu=0.2,
                                load="traction", load_value=1e6,
                                heterogeneous=True)
    elif args.scratch:
        from pcg_mpi_solver_tpu.models.mdf import read_mdf

        cfg.scratch_path = args.scratch
        model = read_mdf(os.path.join(args.scratch, "ModelData", "MDF"))
    else:
        raise SystemExit("warmup: pass a <scratch> dir or --demo-nx N")
    # the scratch MeshPart map belongs to the scratch MODEL — when
    # --demo-nx overrode the model above, pairing it with a synthetic
    # cube would index past the cube's element count
    n_parts, elem_part, n_dev, n_dev_used = _resolve_partition_mesh(
        args.n_parts, None if args.demo_nx else args.scratch)
    print(f">warming {model.n_dof} dofs on {n_dev_used}/{n_dev} device(s), "
          f"{n_parts} parts ({cfg.solver.precision_mode} precision) into "
          f"{cfg.cache_dir} ..")
    s = Solver(model, cfg, mesh=make_mesh(n_dev_used), n_parts=n_parts,
               elem_part=elem_part, backend=args.backend)
    print(f">backend: {s.backend}  setup: {s.setup_s:.2f}s "
          f"({s.setup_cache} partition)")
    s.warmup()
    _finish_telemetry(s, args)
    print(format_stats(cfg.cache_dir))
    print(">warm path ready")


def cmd_cache_stats(args):
    from pcg_mpi_solver_tpu.cache.partition_cache import format_stats

    d = _resolve_cache_dir(args)
    if not d:
        raise SystemExit("cache-stats: pass --cache-dir DIR (or set "
                         "PCG_TPU_CACHE_DIR)")
    print(format_stats(d))


def cmd_bench(args):
    from pcg_mpi_solver_tpu.bench import main as bench_main

    bench_main()


def cmd_lint(args):
    """Contract lint (analysis/): statically prove the solver's
    structural claims — loop-body collective budgets, hot-loop purity,
    f32 dtype discipline, donated-carry aliasing, cache-key/snapshot-
    fingerprint completeness, plus the recovery-path and telemetry-
    schema source/artifact lints.  Runs on CPU (the env is pinned before
    jax initializes); exit 0 = every invariant holds."""
    from pcg_mpi_solver_tpu.analysis.__main__ import run, setup_cpu_env

    setup_cpu_env()
    rc = run(args)
    if rc:
        raise SystemExit(rc)


def cmd_summary(args):
    """Offline summary of an on-disk telemetry/flight JSONL artifact —
    tolerant by design: the exact artifact a killed process leaves has a
    truncated trailing line, which is SKIPPED and counted
    (``truncated_lines``), never raised on.  Flight records present in
    the stream add the mechanical verdict (clean / failed / died) with
    the in-flight record names and last heartbeat.  A base path that a
    multi-process run sharded away (run.jsonl -> run.p<idx>.jsonl)
    falls back to its per-process shards, each summarized in turn."""
    from pcg_mpi_solver_tpu.obs.flight import find_shards
    from pcg_mpi_solver_tpu.obs.metrics import summarize_jsonl

    first = True
    for path in args.files:
        if os.path.exists(path):
            targets = [path]
        else:
            targets = find_shards(path)
            if not targets:
                raise SystemExit(f"summary: {path}: no such file (and "
                                 "no .p<N>.jsonl shard siblings)")
            if not first:
                print()
            print(f">summary: {path}: sharded by a multi-process run — "
                  f"{len(targets)} per-process shard(s)")
            first = False
        for t in targets:
            if not first:
                print()
            first = False
            if len(targets) > 1:
                print(f"--- {t}")
            print(summarize_jsonl(t))


def cmd_telemetry_merge(args):
    """Aggregate per-process telemetry/flight shards (multi-process
    jax.distributed writes run.p<idx>.jsonl per process) into ONE
    time-ordered JSONL stream, each event tagged with its source shard.
    Truncated lines — the killed-process signature — are skipped and
    counted, never raised on."""
    from pcg_mpi_solver_tpu.obs.flight import find_shards, merge_shards

    paths = []
    for p in args.paths:
        shards = find_shards(p)
        for s in (shards or ([p] if os.path.exists(p) else [])):
            if s not in paths:
                paths.append(s)
    if not paths:
        raise SystemExit("telemetry-merge: no shards found for "
                         f"{args.paths} (expected FILE.jsonl and/or "
                         "FILE.p<N>.jsonl siblings)")
    align = None if args.align == "none" else args.align
    stats = merge_shards(paths, args.out, align=align)
    for name in sorted(stats["shards"]):
        st = stats["shards"][name]
        print(f">shard {name}: {st['events']} event(s), "
              f"{st['truncated']} truncated line(s) skipped")
    al = stats.get("align")
    if al is not None:
        if al["matched_anchors"]:
            offs = "  ".join(f"{n}={v:+.6f}s"
                             for n, v in sorted(al["offsets_s"].items()))
            print(f">clock alignment ({al['mode']}): "
                  f"{al['matched_anchors']} matched anchor(s); "
                  f"offsets vs first shard: {offs}")
        else:
            print(">clock alignment: no matched dispatch anchors across "
                  "shards — falling back to raw t ordering")
    print(f">merged {stats['events']} event(s) from "
          f"{len(stats['shards'])} shard(s) -> {args.out}"
          + (f" ({stats['truncated_lines']} truncated line(s) skipped)"
             if stats["truncated_lines"] else ""))


def cmd_perf_report(args):
    """Measured-vs-model phase attribution (ISSUE 12): time the matvec /
    precond / reduction / axpy sub-programs of a live solver individually
    (obs/phases.py — compiled from the solver's own ops/data) next to the
    analytic cost model's roofline prediction (obs/perf.py), anchored by
    a real whole-iteration measurement.  Runs chiplessly on CPU, so the
    attribution table exists BEFORE a hardware window opens."""
    from pcg_mpi_solver_tpu.obs import perf as _perf
    from pcg_mpi_solver_tpu.obs.phases import run_phase_probe
    from pcg_mpi_solver_tpu.parallel.mesh import make_mesh
    from pcg_mpi_solver_tpu.solver.driver import Solver

    cfg = _load_settings(args.settings, args)
    if cfg.solver.precision_mode != "direct":
        raise SystemExit(
            "perf-report: phase probes need a direct-mode solver (one "
            "dtype, one loop) — drop --precision mixed")
    nrhs = max(1, int(args.nrhs))
    cfg.solver.nrhs = nrhs
    if args.scratch:
        from pcg_mpi_solver_tpu.models.mdf import read_mdf

        cfg.scratch_path = args.scratch
        model = read_mdf(os.path.join(args.scratch, "ModelData", "MDF"))
    else:
        from pcg_mpi_solver_tpu.models.synthetic import make_cube_model

        model = make_cube_model(args.nx, 0, 0, E=30e9, nu=0.2,
                                load="traction", load_value=1e6,
                                heterogeneous=True)
    n_parts, elem_part, n_dev, n_dev_used = _resolve_partition_mesh(
        args.n_parts, args.scratch)
    print(f">perf-report: {model.n_elem} elems / {model.n_dof} dofs on "
          f"{n_dev_used}/{n_dev} device(s), {n_parts} parts "
          f"({cfg.solver.pcg_variant} variant, {cfg.solver.precond} "
          f"precond, nrhs={nrhs})..")
    s = Solver(model, cfg, mesh=make_mesh(n_dev_used), n_parts=n_parts,
               elem_part=elem_part, backend=args.backend)
    print(f">backend: {s.backend}")
    cm = s._cost_model
    if cm is None:
        # The Solver degrades _cost_model to None (with a recorder note)
        # when the derivation raises on an exotic model; the measured
        # table must still print, so degrade the same way here.  Like
        # the Solver, only the cost_model() table lookup stays loud.
        try:
            shp = _perf.shape_from_solver(s)
            prof = _perf.resolve_profile(s.mesh.devices.flat[0].device_kind)
            cm = _perf.cost_model(shp, cfg.solver.pcg_variant,
                                  cfg.solver.precond, nrhs, prof)
        except Exception as e:                          # noqa: BLE001
            print(f">cost model unavailable ({type(e).__name__}: {e}) "
                  "— measured-only table")
            cm = None
    probe = run_phase_probe(s, reps=args.reps, nrhs=nrhs,
                            inner=args.inner)
    trace_rep = None
    if getattr(args, "profile_dir", None):
        # ISSUE 15: the MEASURED column — capture a device trace of one
        # warm solve on this same solver and parse it back
        # (obs/profview.py); capture trouble degrades to the
        # predicted|recorded table, never a crash.
        from pcg_mpi_solver_tpu.obs import profview

        try:
            cap = profview.capture_solve_profile(
                s, args.profile_dir, nrhs=nrhs, recorder=s.recorder)
            trace_rep = profview.profile_report(cap["artifact"])
            profview.emit_prof_report(s.recorder, trace_rep)
        except Exception as e:                          # noqa: BLE001
            print(f">device-trace capture failed ({type(e).__name__}: "
                  f"{e}) — the predicted|recorded table below stands")
    if trace_rep is not None:
        # predicted | recorded | measured: the cost model next to the
        # compiled phase probes next to the parsed-trace attribution
        print()
        print(profview.format_report(trace_rep, predicted=cm,
                                     recorded=probe["phases"]))
        _finish_telemetry(s, args)
        return
    print()
    print(f"{'phase':<10} {'model_ms':>10} {'measured_ms':>12} "
          f"{'share':>7}")
    sum_ms = probe["sum_ms_per_iter"] or 0.0
    model_sum = 0.0
    for ph in _perf.PHASES:
        mm = cm["phases"][ph]["model_ms"] if cm is not None else None
        model_sum += mm or 0.0
        meas = probe["phases"][ph]
        share = (meas / sum_ms) if sum_ms else 0.0
        mm_s = f"{mm:>10.4f}" if mm is not None else f"{'-':>10}"
        print(f"{ph:<10} {mm_s} {meas:>12.4f} {share:>6.0%}")
    msum_s = f"{model_sum:>10.4f}" if cm is not None else f"{'-':>10}"
    print(f"{'sum':<10} {msum_s} {sum_ms:>12.4f}")
    whole = probe.get("whole_ms_per_iter")
    if whole:
        print(f"\n>whole-iteration anchor: {whole:.4f} ms/iter "
              f"({probe.get('whole_iters', '?')} iters, real solve "
              "program)")
        print(f">attribution (phase sum / whole): "
              f"{probe['attribution']:.2f}")
        if cm is not None and cm["predicted_ms_per_iter"]:
            print(f">model ratio (measured whole / predicted): "
                  f"{whole / cm['predicted_ms_per_iter']:.2f} "
                  f"(predicted {cm['predicted_ms_per_iter']:.4f} ms/iter, "
                  f"profile={cm['profile']})")
    _finish_telemetry(s, args)


def cmd_prof_report(args):
    """Offline device-trace report (ISSUE 15, obs/profview.py): parse a
    captured profiler artifact — the trace-viewer JSON(.gz) itself, its
    run dir, or any capture root — into per-phase attribution, the
    measured collective-overlap fraction, and the tolerant reader's
    verdict.  Works on any artifact, chiplessly: truncated files and
    missing device lanes degrade to a NAMED verdict, never a crash.
    When the capture sidecar (profview_meta.json) is present, the
    obs/perf.py cost model is rebuilt from it for the predicted
    column.  jax is never imported — a post-mortem must
    not wait on an accelerator runtime."""
    from pcg_mpi_solver_tpu.obs import profview

    # resolve the artifact and its sidecar ONCE, then hand both to the
    # parser (profile_report short-circuits on a direct file path)
    files = profview.find_trace_files(args.path)
    meta = profview.load_meta(files[0]) if files else None
    rep = profview.profile_report(files[0] if files else args.path,
                                  meta=meta, iters=args.iters)
    predicted = None
    try:
        predicted = profview.predicted_from_meta(meta or {})
    except KeyError as e:
        print(f">predicted column unavailable: unknown name {e} in the "
              "capture sidecar (name tables out of sync?)")
    if meta:
        print(f">profile: {meta.get('pcg_variant')} variant, "
              f"{meta.get('precond')} precond, nrhs={meta.get('nrhs')}, "
              f"{meta.get('backend')} backend, "
              f"{meta.get('n_dof')} dofs on "
              f"{meta.get('n_devices')} device(s) "
              f"[{meta.get('platform')}]")
    print(profview.format_report(rep, predicted=predicted))
    if args.telemetry_out:
        from pcg_mpi_solver_tpu.obs.metrics import (
            JsonlSink, MetricsRecorder)

        rec = MetricsRecorder(sinks=[JsonlSink(args.telemetry_out)])
        profview.emit_prof_report(rec, rep)
        rec.close()
        print(f">telemetry: {args.telemetry_out}")
    if not files:
        raise SystemExit(2)


def cmd_fleet_report(args):
    """Cross-process collective-skew attribution (ISSUE 16,
    obs/fleet.py): align the per-process capture subdirs
    (``p<idx>/…``) a multi-controller ``capture_solve_profile`` run
    writes on matched collective END anchors, split every matched
    collective into transport vs wait, and name the straggler per phase.
    Offline and jax-free like ``prof-report``; a single-process capture
    or a collective-free trace degrades to a NAMED verdict, never a
    crash."""
    from pcg_mpi_solver_tpu.obs import fleet

    rep = fleet.fleet_report(args.path)
    print(fleet.format_fleet_report(rep))
    if args.json:
        with open(args.json, "w", encoding="utf-8") as f:
            json.dump(rep, f, indent=1, sort_keys=True)
        print(f">json: {args.json}")
    if args.telemetry_out:
        from pcg_mpi_solver_tpu.obs.metrics import (
            JsonlSink, MetricsRecorder)

        rec = MetricsRecorder(sinks=[JsonlSink(args.telemetry_out)])
        fleet.emit_fleet_report(rec, rep)
        rec.close()
        print(f">telemetry: {args.telemetry_out}")
    if rep["n_processes"] == 0:
        raise SystemExit(2)


def cmd_watch(args):
    """Live run monitor (ISSUE 16, obs/watch.py): tail the flight/
    telemetry JSONL shards of a running solve — per-dispatch progress,
    completed-step residuals, a stall alarm when ALL shards' heartbeats
    go silent past the threshold, and a cost-model x observed-rate ETA.
    ``--once`` prints one snapshot and exits (exit 3 when that snapshot
    is a stall — the scriptable probe); the default polls until the run
    is done or interrupted.  Read-only on the watched stream."""
    from pcg_mpi_solver_tpu.obs import watch

    rec = None
    if args.telemetry_out:
        from pcg_mpi_solver_tpu.obs.metrics import (
            JsonlSink, MetricsRecorder)

        rec = MetricsRecorder(sinks=[JsonlSink(args.telemetry_out)])
    stalled = False
    try:
        while True:
            snap = watch.watch_snapshot(args.path,
                                        stall_after_s=args.stall_after,
                                        tol=args.tol)
            print(watch.format_watch(snap), flush=True)
            if rec is not None:
                watch.emit_watch_events(rec, snap)
            stalled = snap["status"] == "stalled"
            if args.once or snap["status"] == "done":
                break
            try:
                time.sleep(args.interval)
            except KeyboardInterrupt:
                break
            print(flush=True)
    finally:
        if rec is not None:
            rec.close()
            print(f">telemetry: {args.telemetry_out}")
    if stalled and args.once:
        raise SystemExit(3)


def cmd_trend(args):
    """Bench-trend regression sentinel (ISSUE 15, obs/trend.py): match
    legs across the committed BENCH_r*.json round artifacts (plus an
    optional fresh artifact) by shape/variant/precond/nrhs and print
    per-leg deltas with threshold verdicts.  Exit 1 = at least one
    matched leg REGRESSED; exit 2 = nothing to compare."""
    from pcg_mpi_solver_tpu.obs import trend

    thr = (args.threshold if args.threshold is not None
           else trend.DEFAULT_THRESHOLD)
    rc = trend.main_cli(list(args.artifacts), fresh=args.fresh,
                        threshold=thr)
    if rc:
        raise SystemExit(rc)


def main(argv=None):
    ap = argparse.ArgumentParser(prog="pcg-tpu", description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("ingest", help="unpack a reference MDF model archive")
    p.add_argument("archive")
    p.add_argument("scratch")
    p.set_defaults(fn=cmd_ingest)

    p = sub.add_parser("partition", help="compute element->part map")
    p.add_argument("scratch")
    p.add_argument("n_parts", type=int)
    p.add_argument("--method", choices=["rcb", "graph", "auto"], default="auto",
                   help="rcb = coordinate bisection; graph = native "
                        "multilevel dual-graph partitioner (METIS-equivalent)")
    p.set_defaults(fn=cmd_partition)

    p = sub.add_parser("solve", help="run the SPMD PCG solve")
    p.add_argument("scratch")
    p.add_argument("run_id")
    p.add_argument("--settings", default=None)
    p.add_argument("--n-parts", type=int, default=None)
    p.add_argument("--tol", type=float, default=None)
    p.add_argument("--max-iter", type=int, default=None)
    p.add_argument("--precision", choices=["direct", "mixed"], default=None)
    p.add_argument("--precond", choices=_precond_choices(), default=None,
                   help="preconditioner: scalar Jacobi (reference "
                        "parity), 3x3 node-block Jacobi (stronger on "
                        "heterogeneous elasticity), or mg — geometric "
                        "multigrid V-cycle on the lattice hierarchy "
                        "(>=5x fewer iterations on lattice models; "
                        "docs/RUNBOOK.md 'Choosing a preconditioner')")
    _add_variant_flag(p)
    p.add_argument("--speed-test", action="store_true",
                   help="disable all exports for clean timing "
                        "(reference SpeedTestFlag)")
    p.add_argument("--checkpoint-every", type=int, default=0,
                   help="write a solver checkpoint every N time steps")
    _add_resilience_flags(p, "mid-Krylov chunk boundaries", elastic=True)
    p.add_argument("--backend",
                   choices=["auto", "structured", "hybrid", "general"],
                   default="auto",
                   help="matvec backend (auto: structured for uniform "
                        "grids, hybrid for octrees, else general)")
    p.add_argument("--profile-dir", default=None,
                   help="write a jax.profiler trace of the solve here "
                        "(open with TensorBoard; shows the per-op "
                        "compute/collective split; ignored with --speed-test)")
    _add_telemetry_flags(p)
    _add_cache_flag(p)
    _add_preflight_flag(p)
    p.set_defaults(fn=cmd_solve)

    p = sub.add_parser("solve-many",
                       help="batched multi-RHS solve: many load cases "
                            "against one shared partitioned operator "
                            "(per-RHS convergence masks; collective "
                            "count independent of the block width)")
    p.add_argument("scratch")
    p.add_argument("run_id")
    p.add_argument("--rhs", default=None, metavar="FILE.npy",
                   help="load-case block: (n_dof, nrhs) array, one "
                        "column per case ((nrhs, n_dof) is transposed)")
    p.add_argument("--scales", default=None, metavar="S0,S1,...",
                   help="alternative block: columns = scale * the "
                        "model's reference load F")
    p.add_argument("--settings", default=None)
    p.add_argument("--n-parts", type=int, default=None)
    p.add_argument("--tol", type=float, default=None)
    p.add_argument("--max-iter", type=int, default=None)
    p.add_argument("--precision", choices=["direct", "mixed"], default=None)
    p.add_argument("--precond", choices=_precond_choices(), default=None)
    _add_variant_flag(p)
    p.add_argument("--backend",
                   choices=["auto", "structured", "hybrid", "general"],
                   default="auto")
    _add_resilience_flags(p, "blocked-solve chunk boundaries")
    _add_telemetry_flags(p)
    _add_cache_flag(p)
    _add_preflight_flag(p)
    p.set_defaults(fn=cmd_solve_many)

    p = sub.add_parser("serve",
                       help="multi-tenant solve daemon: admit filesystem-"
                            "submitted jobs against one warm operator "
                            "(cost-model deadline pricing, bounded queue "
                            "with load shedding, nrhs packing, crash-"
                            "durable exactly-once journal)")
    p.add_argument("scratch", nargs="?", default=None,
                   help="scratch dir with an ingested model (or use "
                        "--synthetic)")
    p.add_argument("--spool", required=True, metavar="DIR",
                   help="service root: incoming/, results/, "
                        "journal.jsonl")
    p.add_argument("--synthetic", default=None, metavar="NX[,NY,NZ]",
                   help="serve a synthetic heterogeneous cube instead "
                        "of a scratch model")
    p.add_argument("--queue-max", type=int, default=16,
                   help="bounded admission queue depth (default 16); "
                        "arrivals beyond it shed past-deadline jobs or "
                        "are rejected queue_full")
    p.add_argument("--widths", default="1,2,4,8",
                   help="standard nrhs block widths jobs are packed "
                        "into (default 1,2,4,8; the AOT cache compiles "
                        "once per width)")
    p.add_argument("--expected-iters", type=int, default=None,
                   help="iteration count admission prices deadlines "
                        "against (default: the solver max_iter cap — "
                        "conservative)")
    p.add_argument("--poll-s", type=float, default=0.05,
                   help="incoming-directory poll interval (default 0.05)")
    p.add_argument("--idle-exit-s", type=float, default=None,
                   help="drain after this long idle (default: serve "
                        "forever until SIGTERM)")
    p.add_argument("--max-blocks", type=int, default=None,
                   help="drain after dispatching N blocks (bench/test "
                        "knob)")
    p.add_argument("--settings", default=None)
    p.add_argument("--n-parts", type=int, default=None)
    p.add_argument("--tol", type=float, default=None)
    p.add_argument("--max-iter", type=int, default=None)
    p.add_argument("--precision", choices=["direct", "mixed"], default=None)
    p.add_argument("--precond", choices=_precond_choices(), default=None)
    _add_variant_flag(p)
    p.add_argument("--backend",
                   choices=["auto", "structured", "hybrid", "general"],
                   default="auto")
    _add_telemetry_flags(p)
    _add_cache_flag(p)
    _add_preflight_flag(p)
    p.set_defaults(fn=cmd_serve)

    p = sub.add_parser("submit",
                       help="submit one job to a solve-service spool "
                            "(atomic drop; import-light — works from a "
                            "login node)")
    p.add_argument("--spool", required=True, metavar="DIR")
    p.add_argument("--scale", type=float, default=None,
                   help="load case = scale * the model's reference "
                        "load F")
    p.add_argument("--rhs", default=None, metavar="FILE.npy",
                   help="load case = an (n_dof,) .npy column (exactly "
                        "one of --scale / --rhs)")
    p.add_argument("--deadline-s", type=float, default=3600.0,
                   help="relative deadline admission prices against "
                        "(default 3600)")
    p.add_argument("--job-id", default=None,
                   help="explicit job id (default: generated); "
                        "resubmitting a consumed id is dropped — "
                        "exactly-once is per id")
    p.set_defaults(fn=cmd_submit)

    p = sub.add_parser("jobs",
                       help="job table of a solve-service spool, folded "
                            "from the crash-durable journal")
    p.add_argument("--spool", required=True, metavar="DIR")
    p.set_defaults(fn=cmd_jobs)

    p = sub.add_parser("validate",
                       help="run the validate/ preflight checks against "
                            "a scratch model (dry run; no partition, no "
                            "compile)")
    p.add_argument("scratch")
    p.add_argument("--settings", default=None)
    p.add_argument("--tol", type=float, default=None)
    p.add_argument("--max-iter", type=int, default=None)
    p.add_argument("--precision", choices=["direct", "mixed"], default=None)
    _add_preflight_flag(p)
    p.set_defaults(fn=cmd_validate)

    p = sub.add_parser("dynamics",
                       help="explicit central-difference time history "
                            "(preemption-safe: timestep-granular "
                            "snapshots + --resume)")
    p.add_argument("scratch")
    p.add_argument("run_id")
    p.add_argument("--n-steps", type=int, required=True,
                   help="number of explicit timesteps to integrate")
    p.add_argument("--dt", type=float, default=None,
                   help="timestep (default: the model's dt, else the "
                        "CFL estimate; an explicit value above the CFL "
                        "bound is rejected by preflight)")
    p.add_argument("--damping", type=float, default=0.0,
                   help="mass-proportional damping coefficient c_m")
    p.add_argument("--export-every", type=int, default=0,
                   help="displacement frames every k steps (0 = none)")
    p.add_argument("--probe-dofs", default="",
                   help="comma-separated dof ids sampled every step")
    p.add_argument("--settings", default=None)
    p.add_argument("--n-parts", type=int, default=None)
    p.add_argument("--backend", choices=["auto", "hybrid", "general"],
                   default="auto")
    _add_resilience_flags(p, "timesteps")
    _add_telemetry_flags(p)
    _add_preflight_flag(p)
    p.set_defaults(fn=cmd_dynamics)

    p = sub.add_parser("newmark",
                       help="implicit Newmark-beta time history, one "
                            "PCG solve per step (preemption-safe: "
                            "timestep-granular snapshots + --resume)")
    p.add_argument("scratch")
    p.add_argument("run_id")
    p.add_argument("--n-steps", type=int, required=True,
                   help="number of implicit timesteps to integrate")
    p.add_argument("--dt", type=float, default=None,
                   help="timestep (default: the model's dt; "
                        "unconditionally stable at beta=1/4 gamma=1/2, "
                        "so dt is a resolution choice, not a CFL bound)")
    p.add_argument("--beta", type=float, default=0.25)
    p.add_argument("--gamma", type=float, default=0.5)
    p.add_argument("--damping", type=float, default=0.0,
                   help="mass-proportional damping coefficient c_m")
    p.add_argument("--settings", default=None)
    p.add_argument("--n-parts", type=int, default=None)
    p.add_argument("--tol", type=float, default=None)
    p.add_argument("--max-iter", type=int, default=None)
    p.add_argument("--precision", choices=["direct", "mixed"], default=None)
    p.add_argument("--precond", choices=_precond_choices(), default=None)
    _add_variant_flag(p)
    p.add_argument("--backend", choices=["auto", "hybrid", "general"],
                   default="auto")
    _add_resilience_flags(p, "timesteps")
    _add_telemetry_flags(p)
    _add_preflight_flag(p)
    p.set_defaults(fn=cmd_newmark)

    p = sub.add_parser("export", help="export result frames to VTK")
    p.add_argument("scratch")
    p.add_argument("run_id")
    p.add_argument("vars", help='e.g. "U PS1 ES"')
    p.add_argument("mode", choices=["Full", "Boundary", "MidSlices", "Delaunay"])
    p.set_defaults(fn=cmd_export)

    p = sub.add_parser("demo", help="synthetic end-to-end demo")
    p.add_argument("--nx", type=int, default=16)
    p.add_argument("--ny", type=int, default=0)
    p.add_argument("--nz", type=int, default=0)
    p.add_argument("--scratch", default="./scratch")
    p.add_argument("--settings", default=None)
    p.add_argument("--tol", type=float, default=None)
    p.add_argument("--max-iter", type=int, default=None)
    p.add_argument("--precision", choices=["direct", "mixed"], default="mixed")
    p.add_argument("--precond", choices=_precond_choices(), default=None)
    _add_variant_flag(p)
    p.add_argument("--octree", action="store_true",
                   help="graded octree model with transition pattern types "
                        "(nx/ny/nz = base cells; solved on the hybrid "
                        "level-grid backend)")
    p.add_argument("--max-level", type=int, default=2,
                   help="octree refinement levels (with --octree)")
    p.add_argument("--poisson", action="store_true",
                   help="scalar Poisson/diffusion model (1 dof per node, "
                        "heterogeneous conductivity)")
    _add_telemetry_flags(p)
    _add_cache_flag(p)
    _add_preflight_flag(p)
    p.set_defaults(fn=cmd_demo)

    p = sub.add_parser("warmup", help="pre-bake the warm-path caches "
                                      "(partition + AOT step + XLA "
                                      "compile) before a hardware window")
    p.add_argument("scratch", nargs="?", default=None,
                   help="scratch dir with an ingested MDF model "
                        "(or use --demo-nx)")
    p.add_argument("--demo-nx", type=int, default=0,
                   help="warm a synthetic nx^3 cube instead of a scratch "
                        "model (smoke/testing)")
    p.add_argument("--settings", default=None)
    p.add_argument("--n-parts", type=int, default=None)
    p.add_argument("--tol", type=float, default=None)
    p.add_argument("--max-iter", type=int, default=None)
    p.add_argument("--precision", choices=["direct", "mixed"], default=None)
    p.add_argument("--precond", choices=_precond_choices(), default=None)
    _add_variant_flag(p)
    p.add_argument("--backend",
                   choices=["auto", "structured", "hybrid", "general"],
                   default="auto")
    _add_telemetry_flags(p)
    _add_cache_flag(p)
    _add_preflight_flag(p)
    p.set_defaults(fn=cmd_warmup)

    p = sub.add_parser("cache-stats", help="show the warm-path cache table")
    _add_cache_flag(p)
    p.set_defaults(fn=cmd_cache_stats)

    p = sub.add_parser("bench", help="benchmark harness (prints one JSON line)")
    p.set_defaults(fn=cmd_bench)

    p = sub.add_parser("lint",
                       help="contract lint (analysis/): statically prove "
                            "collective budgets, hot-loop purity, dtype "
                            "discipline, donation aliasing and cache-key/"
                            "fingerprint completeness on CPU (see "
                            "docs/ANALYSIS.md)")
    # ONE option surface shared with `python -m pcg_mpi_solver_tpu.analysis`
    # (the same runner) — defined once so the two entry points cannot
    # drift.  analysis/ imports are jax-free, so this is safe here.
    from pcg_mpi_solver_tpu.analysis.__main__ import add_lint_args

    add_lint_args(p)
    p.set_defaults(fn=cmd_lint)

    p = sub.add_parser("perf-report",
                       help="measured-vs-model phase attribution: time "
                            "the matvec/precond/reduction/axpy "
                            "sub-programs of a live solver against the "
                            "analytic cost model's prediction "
                            "(obs/perf.py + obs/phases.py; runs "
                            "chiplessly on CPU)")
    p.add_argument("scratch", nargs="?", default=None,
                   help="scratch dir with an ingested MDF model "
                        "(default: a synthetic --nx cube)")
    p.add_argument("--nx", type=int, default=12,
                   help="synthetic heterogeneous cube size when no "
                        "scratch dir is given (default 12 — below ~10 "
                        "the while-loop carry machinery the four phases "
                        "deliberately exclude dominates the anchor and "
                        "the attribution ratio goes soft)")
    p.add_argument("--settings", default=None)
    p.add_argument("--n-parts", type=int, default=None)
    p.add_argument("--tol", type=float, default=None)
    p.add_argument("--max-iter", type=int, default=None)
    p.add_argument("--precond", choices=_precond_choices(),
                   default=None)
    _add_variant_flag(p)
    p.add_argument("--nrhs", type=int, default=1,
                   help="probe the blocked (multi-RHS) programs at this "
                        "block width")
    p.add_argument("--inner", type=int, default=16,
                   help="inner applications per timed dispatch "
                        "(amortizes host dispatch overhead)")
    p.add_argument("--reps", type=int, default=5,
                   help="interleaved measurement rounds (each times "
                        "every phase plus one whole-iteration anchor; "
                        "per-quantity best-of across rounds)")
    p.add_argument("--backend",
                   choices=["auto", "structured", "hybrid", "general"],
                   default="general",
                   help="matvec backend for the probed solver (default "
                        "general — the probe works on any, general is "
                        "the portable reference)")
    p.add_argument("--profile-dir", default=None, metavar="DIR",
                   help="also capture a device trace of one warm solve "
                        "into DIR and parse it back (obs/profview.py): "
                        "the table gains the MEASURED column next to "
                        "predicted (cost model) and recorded (phase "
                        "probes), plus the collective-overlap verdict")
    _add_telemetry_flags(p)
    _add_cache_flag(p)
    _add_preflight_flag(p)
    p.set_defaults(fn=cmd_perf_report, precision=None)

    p = sub.add_parser("prof-report",
                       help="parse a captured jax.profiler trace "
                            "artifact into per-phase attribution + the "
                            "measured collective-overlap verdict "
                            "(offline, tolerant — a truncated artifact "
                            "degrades to a named verdict)")
    p.add_argument("path",
                   help="trace artifact: the *.trace.json(.gz) file, "
                        "its run dir, or any capture root (e.g. the "
                        "--profile-dir / BENCH_PROFILE_DIR directory)")
    p.add_argument("--iters", type=int, default=None,
                   help="iteration count override for per-iteration "
                        "normalization (default: the capture sidecar's)")
    p.add_argument("--telemetry-out", default=None, metavar="FILE.jsonl",
                   help="also emit the schema-versioned prof_report "
                        "event + prof.* gauges here")
    p.set_defaults(fn=cmd_prof_report)

    p = sub.add_parser("fleet-report",
                       help="cross-process collective-skew attribution "
                            "over a multi-controller capture root "
                            "(p<idx>/ subdirs): clock-align on matched "
                            "collective ends, split transport vs wait, "
                            "name the straggler per phase (offline, "
                            "jax-free, tolerant)")
    p.add_argument("path",
                   help="capture root holding the per-process p<idx>/ "
                        "subdirs (e.g. the --profile-dir / "
                        "BENCH_PROFILE_DIR directory)")
    p.add_argument("--json", default=None, metavar="FILE.json",
                   help="also write the full report as JSON")
    p.add_argument("--telemetry-out", default=None, metavar="FILE.jsonl",
                   help="also emit the schema-versioned fleet_report "
                        "event + fleet.* gauges here")
    p.set_defaults(fn=cmd_fleet_report)

    p = sub.add_parser("watch",
                       help="live run monitor: tail the flight/telemetry "
                            "JSONL shards of a running solve — progress, "
                            "stall alarm (all shards silent past the "
                            "threshold), and a cost-model x observed-"
                            "rate ETA")
    p.add_argument("path", metavar="FILE.jsonl",
                   help="base telemetry/flight path; on-disk .p<N> "
                        "shards are tailed together")
    p.add_argument("--once", action="store_true",
                   help="print one snapshot and exit (exit 3 when it is "
                        "a stall)")
    p.add_argument("--interval", type=float, default=5.0,
                   help="poll interval in seconds (default 5)")
    p.add_argument("--stall-after", type=float, default=None,
                   metavar="S",
                   help="flag a stall when ALL shards are silent this "
                        "long (default: 3x the flight heartbeat "
                        "cadence)")
    p.add_argument("--tol", type=float, default=1e-8,
                   help="convergence target the ETA aims the observed "
                        "rate at (the stream does not carry the run's "
                        "tol; default matches SolverConfig)")
    p.add_argument("--telemetry-out", default=None, metavar="FILE.jsonl",
                   help="emit watch/stall events here (never to the "
                        "watched stream)")
    p.set_defaults(fn=cmd_watch)

    p = sub.add_parser("trend",
                       help="bench-trend regression sentinel: match "
                            "legs across committed BENCH_r*.json round "
                            "artifacts (by shape/variant/precond/nrhs) "
                            "and print threshold-based regressed/"
                            "improved/flat verdicts; exit 1 on a "
                            "regression")
    p.add_argument("artifacts", nargs="*", metavar="BENCH_rNN.json",
                   help="round artifacts in round order (default: "
                        "./BENCH_r*.json sorted)")
    p.add_argument("--fresh", default=None, metavar="FILE.json",
                   help="a fresh artifact (raw bench line or round "
                        "wrapper) appended as the newest round — the "
                        "before/after answer for a live window")
    p.add_argument("--threshold", type=float, default=None,
                   help="relative change separating flat from "
                        "regressed/improved (default 0.10)")
    p.set_defaults(fn=cmd_trend)

    p = sub.add_parser("summary",
                       help="offline summary of a telemetry/flight JSONL "
                            "artifact — tolerant of the truncated "
                            "trailing line a killed process leaves "
                            "(skipped + counted, never raised on)")
    p.add_argument("files", nargs="+", metavar="FILE.jsonl")
    p.set_defaults(fn=cmd_summary)

    p = sub.add_parser("telemetry-merge",
                       help="aggregate per-process telemetry shards "
                            "(FILE.p<N>.jsonl, written under "
                            "multi-process jax.distributed) into one "
                            "time-ordered stream")
    p.add_argument("paths", nargs="+", metavar="FILE.jsonl",
                   help="base path(s); on-disk .p<N> siblings are "
                        "discovered automatically")
    p.add_argument("--out", required=True, metavar="MERGED.jsonl")
    p.add_argument("--align", choices=["none", "collectives"],
                   default="none",
                   help="'collectives': clock-align shards on matched "
                        "dispatch completions (the fleet-report anchor "
                        "model) before ordering, so skewed host clocks "
                        "interleave in true order; events gain "
                        "t_aligned, raw t is preserved")
    p.set_defaults(fn=cmd_telemetry_merge)

    args = ap.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
