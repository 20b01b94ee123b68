"""The traffic kind's warm-up calls before the window (for ``single``,
one request at zero load), which run every program of a request once and
compile what ``warmup()`` left (host clock)."""


def read(run):
    return run["setup"]["warm_s"]
