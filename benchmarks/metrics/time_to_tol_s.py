"""Wall time of the window per solve completed: from the start of the
first solve to the answer of the last, host work between solves
included (host clock)."""


def read(run):
    return run["window_s"] / len(run["solves"]) if run["solves"] else None
