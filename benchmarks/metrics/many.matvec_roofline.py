"""Share of the blocked matvec's roofline: the least time of the traced
block's lockstep f32 matvecs on one chip over their device time under
``pcg/matvec``, mean over the chips (device trace).

One lockstep matvec applies the operator to all R columns at once: its
compulsory bytes are the R input and output vectors plus one f32
material coefficient per element, read once for the block
(``R * 2 * n_dof * 4 + n_elem * 4``), and its flops R times one
column's (``R * sum 2 d^2 n``); the least time is the larger bound over
the chip's published peaks (harness/roofline.py).  One matvec per
lockstep iteration is counted, a lower bound on the work (the loop's
deferred-check trips and the refinement's f64 matvecs are left out)."""

from harness import roofline


def blocked_matvec_work(n_dof: float, type_sizes, R: int) -> dict:
    """Compulsory bytes and flops of one blocked f32 matvec of width R."""
    one = roofline.matvec_work(n_dof, type_sizes)
    n_elem = sum(n for _, n in type_sizes)
    return {"bytes": R * 2 * n_dof * roofline.F32_BYTES
            + n_elem * roofline.F32_BYTES,
            "flops": R * one["flops"]}


def read(run):
    t = run["trace"]
    recs = [r for r in run["traced_solves"]
            if r.get("lockstep_iters") is not None]
    if not t or not t["scoped_ops"] or not recs or not run["peak"]:
        return None
    mv = t["phase_s"]["matvec"]
    if mv <= 0:
        return None
    n = run["chips"]
    types = [(d, k / n) for d, k in run["type_sizes"]]
    least = sum(roofline.least_time_s(
        blocked_matvec_work(run["n_dof"] / n, types, r["block"]),
        run["peak"]) * r["lockstep_iters"] / r["block"] for r in recs)
    return 100.0 * least / mv
