"""idle_pct.solve: share of the traced window in which no operation ran
on the device, under cold solves."""


def read(run):
    trace = run["trace"]
    if trace["devices"] == 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
