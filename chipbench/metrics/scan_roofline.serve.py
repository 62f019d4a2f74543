"""Kernel: the naive executor's least time (``work.py``) over its device
time, summed over the traced window, in %."""

from chipbench.readers import scan_roofline


def read(run):
    return scan_roofline(run)
