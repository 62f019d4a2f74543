"""Median latency of all requests of the window (open loop: due time to
result on the host; closed loop: step start to the step's results)."""

from chipbench.readers import percentile_ms


def read(run):
    return percentile_ms(run.latency_s, 50)
