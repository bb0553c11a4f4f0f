"""scorer_ms.serve: device milliseconds of the scorer program per flush:
its device time in the traced window over its runs there."""
from bench import devtrace


def read(run):
    sec, runs = devtrace.program_seconds(run["trace"], run["scorer_program"])
    if runs == 0:
        return None
    return 1e3 * sec / runs
