"""Arithmetic the metric readers share (``metrics/<name>.py``)."""

from __future__ import annotations

from typing import Optional

import numpy as np

from chipbench import work

#: a request with no answer counts as having waited this long past its
#: due time (the harness waits a minute past the window's close)
UNANSWERED_S = 60.0


def percentile_ms(values_s, q: float) -> Optional[float]:
    """The ``q``-th percentile of all values (linear between ranks), in
    ms; an infinite value (no answer) reads as :data:`UNANSWERED_S`."""
    if values_s is None or len(values_s) == 0:
        return None
    a = np.where(np.isfinite(values_s), values_s, UNANSWERED_S)
    return float(1e3 * np.percentile(a, q))


def scan_roofline(run) -> Optional[float]:
    """Sum of the executor's least times over its device time, in %."""
    tr = run.trace
    if tr is None or run.peak is None or not run.batches \
            or tr.executor_s <= 0:
        return None
    c = run.config
    least = sum(n * work.least_time_s(b, int(c["rows"]), int(c["rank"]),
                                      int(c["k"]), run.peak)
                for b, n in run.batches)
    return 100.0 * least / tr.executor_s


def topk_share(run) -> Optional[float]:
    tr = run.trace
    if tr is None or tr.executor_s <= 0:
        return None
    return 100.0 * tr.topk_s / tr.executor_s


def idle_share(run) -> Optional[float]:
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
