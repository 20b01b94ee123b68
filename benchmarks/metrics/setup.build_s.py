"""``Solver(...)``: partition, device upload (host clock, to the data on
the device)."""


def read(run):
    return run["setup"]["build_s"]
