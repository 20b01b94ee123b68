"""Inner iterations a ``solve_many`` block ran in lockstep, mean over
the blocks of the window: per Krylov cycle the most any column ran,
summed over the cycles, as the program counts it
(``ManySolveResult.lockstep_iters``).  Each of a block's column records
carries its block's count and width, so a block counts once."""


def read(run):
    recs = [r for r in run["solves"] if r.get("lockstep_iters") is not None]
    if not recs:
        return None
    blocks = sum(1.0 / r["block"] for r in recs)
    return sum(r["lockstep_iters"] / r["block"] for r in recs) / blocks
