"""between_ms.path: host milliseconds the path driver spends on a point
outside the engine loop (margin refresh, weight pull, held-out scoring,
bookkeeping: `PathPoint.between_s`), the mean over every point of the
window's sweeps."""


def read(run):
    spent = [b for s in run.get("sweeps") or () for b in s["between_s"]
             if b is not None]
    if not spent:
        return None
    return 1e3 * sum(spent) / len(spent)
