"""Share of a ``solve_many`` block's lanes that did live work: the
columns' inner iterations over block width times lockstep iterations,
over the window's blocks (program counters).  A column that converged
before the block's slowest rides the rest of the cycle masked."""


def read(run):
    recs = [r for r in run["solves"] if r.get("lockstep_iters") is not None]
    lanes = sum(r["lockstep_iters"] for r in recs)
    if not lanes:
        return None
    return sum(r["iters"] for r in recs) / lanes
