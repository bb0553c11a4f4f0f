"""outer_roofline: the least time of one outer iteration's
algorithmic work at the chip's peaks (`bench.work`), as a share of the
outer step's device time per iteration."""
from bench import devtrace, work


def read(run):
    sec, runs = devtrace.program_seconds(run["trace"], run["outer_program"])
    if runs == 0 or sec <= 0:
        return None
    least = work.least_seconds(run["work"], work.peaks(run["device_kind"]))
    return 100.0 * least / (sec / runs)
