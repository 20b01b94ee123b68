"""Device time under the ``pcg/precond``, ``pcg/reduce`` and
``pcg/axpy`` scopes per inner iteration, mean over the chips (device
trace)."""


def read(run):
    t, iters = run["trace"], sum(r["iters"] for r in run["traced_solves"])
    if not t or not t["scoped_ops"] or not iters:
        return None
    p = t["phase_s"]
    return (p["precond"] + p["reduce"] + p["axpy"]) * 1e3 / iters
