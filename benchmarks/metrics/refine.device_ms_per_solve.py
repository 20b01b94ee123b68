"""Device time under no ``pcg/*`` scope per solve: the f64 refinement
residuals, the start programs and the solution's assembly, mean over the
chips (device trace)."""


def read(run):
    t, n = run["trace"], len(run["traced_solves"])
    if not t or not t["scoped_ops"] or not n:
        return None
    return t["other_s"] * 1e3 / n
