"""outer_iters.solve: outer iterations per solve, the mean over the
window's completed solves (`SolveResult.n_outer`)."""


def read(run):
    solves = run.get("solves")
    if not solves:
        return None
    return sum(s["n_outer"] for s in solves) / len(solves)
