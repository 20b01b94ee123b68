"""Device time of the all-reduce and collective-permute ops (the
scalar psums and the slab halo) per inner iteration, mean over the
chips (device trace)."""


def read(run):
    t, iters = run["trace"], sum(r["iters"] for r in run["traced_solves"])
    if not t or run["chips"] < 2 or not iters:
        return None
    return t["collective_s"] * 1e3 / iters
