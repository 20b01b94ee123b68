"""Process start to the first timed request: JAX start-up, model
generation, solver construction, compilation and the traffic kind's
warm-up calls (host clock)."""


def read(run):
    return run["setup"]["total_s"]
