"""outer_iters.path: outer iterations per sweep, the sum of its points'
`n_outer`, the mean over the window's completed sweeps."""


def read(run):
    sweeps = run.get("sweeps")
    if not sweeps:
        return None
    return sum(sum(s["n_outer"]) for s in sweeps) / len(sweeps)
