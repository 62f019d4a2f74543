"""Naive exact top-K: score every target, keep the best K.

The paper's baseline (``O((R + log K) M)``). On TPU this is a single
MXU matmul followed by an exact top-K selection (:func:`select_topk`) —
the strongest possible wall-clock baseline, which is why EXPERIMENTS.md
reports both score counts (the paper's metric) and roofline terms (the
hardware metric).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

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


#: lanes per chunk of the two-stage selection
SELECT_CHUNK = 128
#: the two-stage selection runs where the row holds at least this many
#: times the ``k`` candidate chunks' lanes: on a TPU v5e it ran faster
#: than one ``top_k`` at every candidate share measured, up to a half
#: (DESIGN.md §4)
SELECT_MIN_RATIO = 2


def select_chunk(m: int, k: int) -> int:
    """Chunk width of :func:`select_topk` over ``m`` scores at ``k``, or 0
    where it runs ``lax.top_k`` directly (DESIGN.md §4). Shapes alone
    decide, so the host names the path without touching the device."""
    s = SELECT_CHUNK
    return s if m % s == 0 and SELECT_MIN_RATIO * k * s <= m else 0


def select_path(m: int, k: int) -> str:
    """``"two_stage"`` or ``"direct"``: the path :func:`select_topk` takes."""
    return "two_stage" if select_chunk(m, k) else "direct"


def select_topk(scores: Array, k: int) -> Tuple[Array, Array]:
    """Exactly ``lax.top_k(scores, k)`` over the last axis: the same
    values, and the same ids wherever each ``top_k`` involved breaks
    ties to the lower id (DESIGN.md §4 names where a TPU does not).

    Where :func:`select_chunk` engages, two stages replace the one
    ``top_k`` over every lane: the maxima of ``S``-lane chunks, the top
    ``k`` chunks by maximum, then ``top_k`` over those chunks' ``k * S``
    scores only. Every item of the top ``k`` lies in a chosen chunk, and
    the chosen chunks are gathered in id order, so candidate order is id
    order (DESIGN.md §4).
    """
    m = scores.shape[-1]
    s = select_chunk(m, k)
    if not s:
        return jax.lax.top_k(scores, k)
    lead = scores.shape[:-1]
    chunks = scores.reshape(lead + (m // s, s))
    _, cid = jax.lax.top_k(jnp.max(chunks, axis=-1), k)
    cid = jnp.sort(cid, axis=-1)
    cand = jnp.take_along_axis(chunks, cid[..., None], axis=-2)
    # materialise the candidates: left to itself, the TPU compiler fuses
    # the gather into the candidate ``TopK``, and a profile then shows a
    # fusion where the selection's ``TopK`` custom call ran (DESIGN.md §4)
    cand = jax.lax.optimization_barrier(cand.reshape(lead + (k * s,)))
    vals, pos = jax.lax.top_k(cand, k)
    ids = jnp.take_along_axis(cid, pos // s, axis=-1) * s + pos % s
    return vals, ids


@functools.partial(jax.jit, static_argnames=("k",))
def naive_topk(targets: Array, u: Array, k: int) -> TopKResult:
    """Exact top-K by full scoring. ``targets: [M, R]``, ``u: [R] or [B, R]``."""
    scores = jnp.einsum("...r,mr->...m", u, targets,
                        precision=SCORE_PRECISION)
    values, indices = select_topk(scores, k)
    m = targets.shape[0]
    batch_shape = scores.shape[:-1]
    n_scored = jnp.full(batch_shape, m, dtype=jnp.int32)
    depth = jnp.zeros(batch_shape, dtype=jnp.int32)
    upper = jnp.full(batch_shape, -jnp.inf, dtype=values.dtype)
    return TopKResult(values, indices, n_scored, depth, upper=upper)
