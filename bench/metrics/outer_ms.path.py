"""outer_ms.path: device milliseconds of one outer iteration of the
shrinking path step: the device time of the outer-step program's runs in
the traced window over the number of those runs."""
from bench import devtrace


def read(run):
    if "sweeps" not in run:
        return None
    sec, runs = devtrace.program_seconds(run["trace"], run["outer_program"])
    if runs == 0:
        return None
    return 1e3 * sec / runs
