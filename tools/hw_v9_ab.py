"""Post-wave v9 hardware A/B: measure the dot-built-gather kernel on the
deployed toolchain and, if it beats the engaged XLA gse form, capture a
v9-engaged flagship bench line.

v9 removes the construct class (concat lane-offset mismatch) an older
toolchain rejected in v6/v8; on the installed toolchain v6 compiles
too, so this A/B decides between kernels that both run.

Steps:
  1. matvec A/B, v9 only, at the 150^3 flagship — the first hardware
     compile AND first hardware execution of any kernel in the family.
  2. ONLY IF v9 compiled and beat gse: flagship bench with the v9
     kernel engaged (PCG_TPU_PALLAS_V=9, pallas=auto probes it) so the
     salvage file carries the better line for the round-end driver.

Usage: python tools/hw_v9_ab.py [--deadline-min 240]
"""

from __future__ import annotations

import argparse
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.hw_session import log_line, run_step, start_queue  # noqa: E402


def _parse_ab(path, marker):
    """(gse_ms, v9_ms or None) from the A/B step's log section.

    Never raises: an unreadable log or a missing marker (the step died
    before writing its section header) is an anomaly of ONE step, and it
    must not abort the remaining independent steps of a scarce hardware
    window — log it and report (None, None), which downstream treats as
    "v9 produced no number"."""
    try:
        text = open(path).read()
        sect = text[text.rindex(marker):]
    except (OSError, ValueError) as e:
        try:
            log_line(path, f"v9 A/B parse anomaly ({type(e).__name__}: "
                           f"{e}) — treating as no-measurement")
        except OSError:
            print(f"v9 A/B parse anomaly ({type(e).__name__}: {e})",
                  flush=True)         # the log file itself is the anomaly
        return None, None
    gse = re.search(r"xla \(gse\):\s+([0-9.]+) ms/matvec", sect)
    v9 = re.search(r"pallas v9 C=8:\s+([0-9.]+) ms/matvec", sect)
    return (float(gse.group(1)) if gse else None,
            float(v9.group(1)) if v9 else None)


_AB_STEP = "matvec A/B v9"


def run_v9_ab(path):
    """A/B step + parse; returns (gse_ms, v9_ms)."""
    run_step(path, _AB_STEP, ["examples/bench_matvec.py", "150"],
             env_extra={"BENCH_MATVEC_VARIANTS": "v9"}, timeout=2400)
    # the trailing colon+space anchors the STEP line — run_step also
    # appends a "... done: rc=..." line a bare prefix would rindex
    gse_ms, v9_ms = _parse_ab(path, f"=== {_AB_STEP}: ")
    log_line(path, f"v9 A/B parse: gse={gse_ms} ms, v9={v9_ms} ms")
    return gse_ms, v9_ms


def maybe_engage_flagship(path, gse_ms, v9_ms):
    """Run the v9-engaged flagship bench only on a measured win; log a
    reason that distinguishes compile-rejection from a perf loss."""
    if v9_ms is None:
        log_line(path, "v9 did not produce a hardware number "
                       "(compile rejection or runtime failure) — "
                       "no engaged flagship run")
        return False
    if gse_ms is not None and v9_ms >= gse_ms:
        log_line(path, f"v9 measured {v9_ms} ms but does NOT beat gse "
                       f"({gse_ms} ms) — no engaged flagship run")
        return False
    # device-less steps must not re-emit salvage as fresh; a LIVE line
    # still WRITES salvage for the round-end driver (bench.py:_write_salvage
    # is unconditional)
    run_step(path, "flagship (v9 engaged)", ["bench.py"],
             env_extra={"BENCH_SALVAGE": "0", "BENCH_CPU_UPGRADE": "0",
                        "PCG_TPU_PALLAS_V": "9",
                        "BENCH_WALL_BUDGET_S": "3480"},
             timeout=3600, force_gate=True)
    return True


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--deadline-min", type=float, default=240)
    ap.add_argument("--log", default=os.path.join("docs", "HW_SESSION.log"))
    args = ap.parse_args()
    path = start_queue("hw_v9_ab", args.deadline_min, args.log)
    gse_ms, v9_ms = run_v9_ab(path)
    maybe_engage_flagship(path, gse_ms, v9_ms)
    log_line(path, "hw_v9_ab complete")


if __name__ == "__main__":
    main()


# smoke: python - <<'EOF'
# import tools.hw_v9_ab as m
# open('/tmp/ablog','w').write(
#     "x\n=== matvec A/B v9: ...\nxla (gse):      13.741 ms/matvec\n"
#     "pallas v9 C=8:    3.2 ms/matvec  (vs xla  4.29x, maxrelerr 1e-07)\n"
#     "=== matvec A/B v9 done: rc=0 (98s)\n")
# assert m._parse_ab('/tmp/ablog', '=== matvec A/B v9: ') == (13.741, 3.2)
# EOF
