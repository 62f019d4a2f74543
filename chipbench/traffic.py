"""The one traffic generator: a mix is data (``traffic/<name>.json``).

Open loop (``"loop": "open"``): single requests into
``AsyncTopKServer.submit`` at ``rate_per_s`` on average. Arrivals are a
Poisson process, optionally switched on and off in bursts
(``"burst": {"period_ms": P, "on_ms": A}``: arrivals only in the first
A ms of every P ms, at P/A times the mean rate). Every seed gets the
same number of arrivals, ``round(rate_per_s * seconds)``, placed as a
Poisson process conditioned on that count (sorted uniform points over
the time arrivals are on), so seeds differ in order and spacing, not in
the amount of work.

Closed loop (``"loop": "closed"``): ``batch`` queries per step into
``TopKServer.query``; the next step is sent when the last one's answers
are on the host, until ``seconds`` have passed. Steps cycle through a
pool of ``pool_steps`` distinct batches.
"""

from __future__ import annotations

import numpy as np

from chipbench import catalogue


def n_arrivals(rate_per_s: float, seconds: float) -> int:
    return max(1, int(round(float(rate_per_s) * seconds)))


def arrival_offsets(traffic: dict, seed: int, seconds: float,
                    rate_per_s: float | None = None) -> np.ndarray:
    """Due times in seconds from the window's start, ascending, in
    ``[0, seconds)``."""
    n = n_arrivals(traffic["rate_per_s"] if rate_per_s is None
                   else rate_per_s, seconds)
    rng = np.random.default_rng([int(seed), catalogue.SCHEDULE])
    burst = traffic.get("burst")
    if not burst:
        return np.sort(rng.uniform(0.0, seconds, n))
    period = float(burst["period_ms"]) / 1e3
    on = float(burst["on_ms"]) / 1e3
    if not 0.0 < on <= period:
        raise ValueError(f"burst on_ms must be in (0, period_ms]: {burst}")
    on_total = (seconds // period) * on + min(seconds % period, on)
    u = np.sort(rng.uniform(0.0, on_total, n))
    phase = np.floor(u / on)
    return phase * period + (u - phase * on)


def query_rows(traffic: dict, config: dict, seed: int,
               seconds: float) -> np.ndarray:
    """The client's query rows, on the host: one per arrival (open
    loop, all distinct), or ``[pool_steps, batch, rank]`` (closed)."""
    rank = int(config["rank"])
    dist = config["queries"]
    block = int(config["block_rows"])
    if traffic["loop"] == "open":
        n = n_arrivals(traffic["rate_per_s"], seconds)
        return catalogue.host_rows(seed, catalogue.QUERIES, n, rank, dist,
                                   block)
    if traffic["loop"] == "closed":
        p, b = int(traffic["pool_steps"]), int(traffic["batch"])
        flat = catalogue.host_rows(seed, catalogue.QUERIES, p * b, rank,
                                   dist, block)
        return flat.reshape(p, b, rank)
    raise ValueError(f"unknown loop {traffic['loop']!r}")
