"""Naive exact top-K: score every target, keep the best K.

The paper's baseline (``O((R + log K) M)``). On TPU this is a single
MXU matmul followed by ``lax.top_k`` — the strongest possible wall-clock
baseline, which is why EXPERIMENTS.md reports both score counts (the paper's
metric) and roofline terms (the hardware metric).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

Array = jnp.ndarray

#: Precision of every scoring contraction in ``repro.core``. On a TPU an
#: f32 ``dot`` at default precision multiplies in bf16, while the engines'
#: pruning bounds (TA thresholds, Cauchy-Schwarz norms) are f32
#: elementwise sums: bf16 scores would be compared against f32 bounds and
#: would not match a float64 reference. HIGHEST keeps products at f32
#: accuracy on every backend.
SCORE_PRECISION = jax.lax.Precision.HIGHEST


class TopKResult(NamedTuple):
    values: Array   # [K] (or [B, K]) scores, descending
    indices: Array  # [K] (or [B, K]) item ids
    n_scored: Array  # scalar (or [B]) int32 — number of s(x,y) evaluations
    depth: Array     # scalar (or [B]) int32 — list depth reached (0 for naive)
    # Scalar (or [B]) upper bound on the score of every item the scan did
    # NOT enumerate when it stopped (-inf when the scan provably saw every
    # candidate).  None for legacy paths that don't track a bound.
    upper: Optional[Array] = None


def certificate_gaps(res: TopKResult) -> Array:
    """Per-slot certificate gap ``upper - value`` for a (possibly halted) scan.

    ``gap <= 0`` certifies the slot: its score is at least the bound on every
    unenumerated item, and since the scan's running top-K already dominates all
    enumerated items, the slot provably belongs to the true top-K.  Values are
    sorted descending, so gaps are ascending and the certified slots always
    form a prefix.  Pad slots (``indices < 0``) get ``+inf`` (never certified;
    also avoids ``-inf - -inf = nan`` when the bound itself is ``-inf``).
    """
    if res.upper is None:
        raise ValueError(
            "result carries no upper bound; run a budget-capable engine "
            "(naive/ta/bta/norm) to obtain certificates")
    gap = jnp.asarray(res.upper)[..., None] - res.values
    return jnp.where(res.indices >= 0, gap, jnp.inf)


def certified_counts(res: TopKResult) -> Array:
    """Number of certified-exact prefix slots per query ([B] or scalar int32)."""
    gaps = certificate_gaps(res)
    return jnp.sum(gaps <= 0, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k",))
def naive_topk(targets: Array, u: Array, k: int) -> TopKResult:
    """Exact top-K by full scoring. ``targets: [M, R]``, ``u: [R] or [B, R]``."""
    scores = jnp.einsum("...r,mr->...m", u, targets,
                        precision=SCORE_PRECISION)
    values, indices = jax.lax.top_k(scores, k)
    m = targets.shape[0]
    batch_shape = scores.shape[:-1]
    n_scored = jnp.full(batch_shape, m, dtype=jnp.int32)
    depth = jnp.zeros(batch_shape, dtype=jnp.int32)
    upper = jnp.full(batch_shape, -jnp.inf, dtype=values.dtype)
    return TopKResult(values, indices, n_scored, depth, upper=upper)
