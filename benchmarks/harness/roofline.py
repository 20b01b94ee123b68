"""Peak table and the compulsory work of one matvec.

The least time of one f32 matvec is the larger of two bounds that hold
whatever kernel implements it:

- bytes: the input and output vectors (``2 * n_dof * 4``) plus one f32
  material coefficient per element (``n_elem * 4``), over peak HBM
  bandwidth;
- flops: ``sum over element types of 2 * d**2 * n_elements_of_type``,
  over the bf16 peak (higher than anything f32 reaches, so the bound is
  safe).

A kernel's roofline share is that least time, times the matvecs it ran,
over the device time it took: a true lower bound over a measured time,
so it cannot pass 100% unless the trace leaves out part of the work.
"""

from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")
F32_BYTES = 4


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``.  A kind that is not
    in the table is an error, never a default."""
    with open(_PEAKS, encoding="utf-8") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} (known: {sorted(table)})")
    return table[device_kind]


def matvec_work(n_dof: int, type_sizes) -> dict:
    """Compulsory bytes and flops of one f32 matvec.

    ``type_sizes``: (d, n_elements) per element type, d the element's
    dof count."""
    n_elem = sum(n for _, n in type_sizes)
    return {
        "bytes": 2 * n_dof * F32_BYTES + n_elem * F32_BYTES,
        "flops": sum(2 * d * d * n for d, n in type_sizes),
    }


def least_time_s(work: dict, peak: dict) -> float:
    """The larger of the bytes bound and the flops bound."""
    return max(work["bytes"] / peak["hbm_bytes_per_s"],
               work["flops"] / peak["flops_per_s"])
