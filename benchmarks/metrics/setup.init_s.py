"""Process start to the chips found: Python and JAX imports and the
start of the accelerator runtime (host clock)."""


def read(run):
    return run["setup"]["init_s"]
