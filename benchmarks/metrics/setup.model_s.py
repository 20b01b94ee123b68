"""Generating the mesh, material field and load (host clock)."""


def read(run):
    return run["setup"]["model_s"]
