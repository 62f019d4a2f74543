"""Work of the naive executor (scoring matmul + ``lax.top_k``) and its
least time on a chip.

Counted over the LIVE rows, not their power-of-two bucket: a change that
stops reading pad rows raises the share and cannot make the count stale.
The float32 product is counted once, against the chip's bf16 peak,
whatever number of bf16 passes ``HIGHEST`` makes: the share can then
only read low, never above 100%.
"""

from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's peaks, from the table keyed by ``device_kind``; a
    device that is not in the table is an error."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device {device_kind!r} is not in {PEAKS_FILE.name}"
                       f" (have {sorted(table)})")
    return table[device_kind]


def naive_flops(b: int, m_live: int, rank: int) -> float:
    return 2.0 * b * m_live * rank


def naive_bytes(b: int, m_live: int, rank: int, k: int) -> float:
    """float32 catalogue and queries in, float32 values + int32 ids out."""
    return 4.0 * (m_live * rank + b * rank) + 8.0 * b * k


def least_time_s(b: int, m_live: int, rank: int, k: int,
                 peak: dict) -> float:
    return max(naive_flops(b, m_live, rank) / float(peak["flops_per_s"]),
               naive_bytes(b, m_live, rank, k) / float(peak["bytes_per_s"]))
