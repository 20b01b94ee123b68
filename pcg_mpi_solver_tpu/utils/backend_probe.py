"""Subprocess backend probe, used only by ``bench.py``'s orchestrator.

The probe initializes JAX's backend in a child process with a timeout
and reports why it failed.  The solve path never calls it: a child that
needs the chip cannot have it while the parent holds it.  It goes with
the bench orchestrator when the benchmark replaces that.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import Tuple


def probe_backend(timeout_s: float = 180.0) -> Tuple[bool, str]:
    """Returns (ok, detail).  detail explains a failure for the operator.
    Skipped when the CPU is requested, or when this process already holds
    a backend (a child would contend with it for the device)."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return True, "cpu backend pinned; probe skipped"
    try:
        from jax._src import xla_bridge

        if getattr(xla_bridge, "_backends", None):
            return True, ("backend already live in this process; "
                          "probe skipped")
    except ImportError:
        pass
    try:
        proc = subprocess.run(
            [sys.executable, "-c", "import jax; jax.devices()"],
            timeout=timeout_s, capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        return False, (f"backend init did not complete within "
                       f"{timeout_s:.0f}s (device held by another "
                       "process, or lost)")
    if proc.returncode != 0:
        tail = (proc.stderr or "").strip().splitlines()[-8:]
        return False, (
            f"backend init failed (rc={proc.returncode}):\n"
            + "\n".join(tail))
    return True, "ok"
