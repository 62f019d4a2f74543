"""Load generator: 99th percentile of actual send minus due time."""

from chipbench.readers import percentile_ms


def read(run):
    return percentile_ms(run.gen_lag_s, 99)
