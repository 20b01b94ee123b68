"""``python -m pcg_mpi_solver_tpu.analysis`` — the contract-lint CLI.

Exit codes: 0 = clean (baselined findings allowed), 1 = findings,
2 = a rule or the engine crashed.  ``pcg-tpu lint`` is the same runner
behind the package CLI.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def setup_cpu_env() -> None:
    """Pin the lint to the CPU backend BEFORE jax initializes: static
    analysis must never take an accelerator, and the traced matrix
    needs a multi-device host platform.  No-op once a backend is live
    (pytest's conftest rig has configured it already)."""
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge._backends:
            return
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("JAX_ENABLE_X64", "1")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    if "jax" in sys.modules:
        # jax binds these env vars at import: apply them as config
        import jax

        jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])
        jax.config.update("jax_enable_x64", True)


def add_lint_args(ap) -> None:
    """The ONE definition of the lint option surface, shared by this
    module's parser and the ``pcg-tpu lint`` subcommand (cli.py) so the
    two documented-as-identical entry points cannot drift."""
    ap.add_argument("--fast", action="store_true",
                    help="pre-hardware-window gate: source/artifact rules "
                         "plus the collective/purity proofs on the "
                         "reduced program matrix (distributed backend; "
                         "skips donation + fingerprint sweeps)")
    ap.add_argument("--json", metavar="FILE", default=None,
                    help="write the machine-readable report here "
                         "('-' = stdout)")
    ap.add_argument("--baseline", metavar="FILE", default=None,
                    help="suppression file (default: the checked-in "
                         "analysis/baseline.json, which ships EMPTY); "
                         "entries need a documented reason")
    ap.add_argument("--rules", default=None, metavar="ID[,ID...]",
                    help="run only these rule ids")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")


def build_parser(prog: str = "pcg_mpi_solver_tpu.analysis"):
    ap = argparse.ArgumentParser(
        prog=prog,
        description="statically prove the solver's performance/resilience "
                    "invariants (collective budgets, hot-loop purity, "
                    "dtype discipline, donation aliasing, cache-key/"
                    "fingerprint completeness, source/artifact lints) — "
                    "see docs/ANALYSIS.md for the rule catalog")
    add_lint_args(ap)
    return ap


def run(args) -> int:
    from pcg_mpi_solver_tpu.analysis import engine

    if args.list_rules:
        for r in engine.list_rules():
            tag = "fast" if r.fast else "full"
            print(f"{r.id:26s} [{r.kind}/{tag}] {r.doc}")
        return 0
    baseline = args.baseline if args.baseline is not None \
        else engine.DEFAULT_BASELINE
    rule_ids = ([s for s in args.rules.split(",") if s]
                if args.rules else None)
    try:
        report = engine.run_lint(fast=args.fast, rule_ids=rule_ids,
                                 baseline_path=baseline)
    except ValueError as e:           # unknown rule id / bad baseline
        print(f"pcg-tpu lint: {e}", file=sys.stderr)
        return 2
    if args.json:
        blob = json.dumps(report.to_dict(), indent=2, sort_keys=True)
        if args.json == "-":
            print(blob)
        else:
            try:
                with open(args.json, "w", encoding="utf-8") as f:
                    f.write(blob + "\n")
            except OSError as e:
                # an unwritable report path is an ENGINE failure (exit
                # 2), not a lint verdict — exit 1 must keep meaning
                # "findings" for CI/hw_session wrappers
                print(report.render())
                print(f"pcg-tpu lint: cannot write --json {args.json}: "
                      f"{e}", file=sys.stderr)
                return 2
    if args.json != "-":
        print(report.render())
    return report.exit_code


def main(argv=None) -> int:
    setup_cpu_env()
    return run(build_parser().parse_args(argv))


if __name__ == "__main__":
    sys.exit(main())
