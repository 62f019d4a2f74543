"""99th percentile latency over all requests of the window."""

from chipbench.readers import percentile_ms


def read(run):
    return percentile_ms(run.latency_s, 99)
