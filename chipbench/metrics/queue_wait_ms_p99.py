"""Request pipeline: 99th percentile of the program's ``queue_wait``
spans over every request of the traced window."""

from chipbench.readers import percentile_ms


def read(run):
    return percentile_ms(run.queue_wait_s, 99)
