"""Share of the traced window in which no op ran on a chip, mean over
the chips (device trace)."""


def read(run):
    t = run["trace"]
    if not t or not t["n_ops"]:
        return None
    return 1.0 - t["busy_s"] / t["window_s"]
