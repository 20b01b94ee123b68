"""Krylov iterations per solve, as the program returns them: the f32
inner iterations summed over the refinement cycles."""


def read(run):
    s = run["solves"]
    return sum(r["iters"] for r in s) / len(s) if s else None
