"""``Solver.warmup()``: compiling, or loading from the compile cache,
every program of the solve (host clock)."""


def read(run):
    return run["setup"]["compile_s"]
