"""active_share.path: the un-shrunk share of the features, in percent,
the mean over every outer iteration of the window's sweeps (each point's
`n_active` per iteration over the feature count)."""


def read(run):
    counts = [a for s in run.get("sweeps") or ()
              for point in s["n_active"] for a in point]
    if not counts:
        return None
    return 100.0 * sum(counts) / (len(counts) * run["n_features"])
