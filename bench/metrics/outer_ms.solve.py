"""outer_ms.solve: device milliseconds of one outer iteration: the
device time of the outer-step program's runs in the traced window over
the number of those runs."""
from bench import devtrace


def read(run):
    sec, runs = devtrace.program_seconds(run["trace"], run["outer_program"])
    if runs == 0:
        return None
    return 1e3 * sec / runs
