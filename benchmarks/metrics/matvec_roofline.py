"""Share of the matvec's roofline: the least time of the inner
iterations' f32 matvecs on one chip (compulsory bytes or flops of the
chip's share, over its published peaks: harness/roofline.py) over their
device time under ``pcg/matvec``, mean over the chips (device trace).
One matvec per classic inner iteration is counted, a lower bound on
the work."""

from harness import roofline


def read(run):
    t, iters = run["trace"], sum(r["iters"] for r in run["traced_solves"])
    if not t or not t["scoped_ops"] or not iters or not run["peak"]:
        return None
    mv = t["phase_s"]["matvec"]
    if mv <= 0:
        return None
    n = run["chips"]
    work = roofline.matvec_work(
        run["n_dof"] / n, [(d, k / n) for d, k in run["type_sizes"]])
    return 100.0 * roofline.least_time_s(work, run["peak"]) * iters / mv
