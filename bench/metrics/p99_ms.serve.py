"""p99_ms.serve: the 99th percentile of the latency of every request due
in the traced window, timed from its due time (a refused or failed
request counts as infinite). Read under the profiler, so it runs a
little above the same tail in an untraced run."""


def read(run):
    return run.get("p99_ms")
