"""Kernel: device time of the executor's top-k op over the executor's
device time, in %."""

from chipbench.readers import topk_share


def read(run):
    return topk_share(run)
