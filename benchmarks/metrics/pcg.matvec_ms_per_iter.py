"""Device time under the ``pcg/matvec`` scope per inner iteration, mean
over the chips (device trace)."""


def read(run):
    t, iters = run["trace"], sum(r["iters"] for r in run["traced_solves"])
    if not t or not t["scoped_ops"] or not iters:
        return None
    return t["phase_s"]["matvec"] * 1e3 / iters
