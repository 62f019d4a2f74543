"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jnp.ndarray


def topk_mips_ref(T_sorted: Array, u: Array, k: int):
    """Exact top-K over the norm-ordered catalogue (ids are positions in
    T_sorted; ops.py maps them back through the permutation)."""
    scores = T_sorted @ u
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx


def fm_interaction_ref(emb: Array):
    """emb: [B, F, d] -> [B] Rendle sum-square second-order term."""
    s = emb.sum(axis=1)
    sq = (emb * emb).sum(axis=1)
    return 0.5 * (s * s - sq).sum(axis=-1)
