"""flush_rows.serve: request rows per flush of the serving loop over the
window (`ServeLoop.stats()`: real rows over flushes)."""


def read(run):
    if not run.get("flushes"):
        return None
    return run["rows"] / run["flushes"]
