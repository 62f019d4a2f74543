"""Exactness and paper-theorem tests for the top-K core (deterministic).

Property-based (hypothesis) variants live in ``test_core_properties.py``
and are skipped automatically when hypothesis is not installed; everything
here runs with numpy-seeded determinism only.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    blocked_topk,
    blocked_topk_batched,
    fagin_topk_np,
    naive_topk,
    norm_pruned_topk,
    partial_threshold_topk_np,
    threshold_topk_from_index,
    threshold_topk_np,
)
from repro.core.index import build_index
from repro.core.naive import select_path, select_topk
from repro.core.toy import TOY_BEST_ITEM, TOY_SCORES, TOY_T, TOY_U, table2_adversarial


def _problem(seed, sparse=False, negate=False):
    rng = np.random.default_rng(seed)
    m = int(rng.integers(5, 120))
    r = int(rng.integers(2, 16))
    k = int(rng.integers(1, min(m, 8) + 1))
    T = rng.standard_normal((m, r)).astype(np.float32)
    u = rng.standard_normal(r).astype(np.float32)
    if sparse:
        u[rng.random(r) < 0.5] = 0.0
        if np.all(u == 0):
            u[0] = 1.0
    if negate:
        u = -np.abs(u)
    return T, u, k


PROBLEMS = ([(s, False, False) for s in range(8)]
            + [(s, True, False) for s in range(8, 14)]
            + [(s, False, True) for s in range(14, 20)])


# ---------------------------------------------------------------------------
# Paper worked examples
# ---------------------------------------------------------------------------


class TestPaperExamples:
    def test_toy_scores_match_paper(self):
        expected = [-4.85, -4.71, -0.73, -5.37, 0.93, 4.7, -0.59, 1.46,
                    1.49, 2.6]
        np.testing.assert_allclose(TOY_SCORES, expected, atol=1e-5)

    def test_toy_threshold_algorithm(self):
        idx = build_index(TOY_T)
        vals, ids, stats = threshold_topk_np(
            TOY_T, np.asarray(idx.order_desc), TOY_U, 1)
        assert ids[0] == TOY_BEST_ITEM
        assert stats.n_scored == 5          # paper: five of ten scored
        assert stats.depth == 2             # paper: terminates in 2 rounds

    def test_toy_fagin(self):
        idx = build_index(TOY_T)
        vals, ids, stats = fagin_topk_np(
            TOY_T, np.asarray(idx.order_desc), TOY_U, 1)
        assert ids[0] == TOY_BEST_ITEM
        assert stats.n_scored == 9          # paper: nine of ten scored
        assert stats.depth == 5             # paper: stops at depth five

    def test_fagin_not_instance_optimal(self):
        """Theorem 3 via the Table 2 construction: TA depth 2, FA ~M/2."""
        T, u = table2_adversarial(400)
        idx = build_index(T)
        order = np.asarray(idx.order_desc)
        _, _, s_ta = threshold_topk_np(T, order, u, 1)
        _, _, s_fa = fagin_topk_np(T, order, u, 1)
        assert s_ta.depth == 2
        assert s_fa.depth >= 180            # ~M/2

    def test_jax_ta_counts_match_oracle_on_toy(self):
        idx = build_index(TOY_T)
        res = threshold_topk_from_index(
            jnp.asarray(TOY_T), idx, jnp.asarray(TOY_U), 1)
        assert int(res.indices[0]) == TOY_BEST_ITEM
        assert int(res.n_scored) == 5 and int(res.depth) == 2


# ---------------------------------------------------------------------------
# Deterministic exactness sweeps (random / sparse / negative queries)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed,sparse,negate", PROBLEMS)
def test_ta_equals_naive(seed, sparse, negate):
    T, u, k = _problem(seed, sparse, negate)
    nv = np.sort(np.asarray(naive_topk(jnp.asarray(T), jnp.asarray(u), k).values))
    idx = build_index(T)
    tv, _, ts = threshold_topk_np(T, np.asarray(idx.order_desc), u, k)
    np.testing.assert_allclose(np.sort(tv), nv, atol=1e-4)
    jr = threshold_topk_from_index(jnp.asarray(T), idx, jnp.asarray(u), k)
    np.testing.assert_allclose(np.sort(np.asarray(jr.values)), nv, atol=1e-4)
    # the JAX TA is count-faithful to the oracle
    assert int(jr.n_scored) == ts.n_scored
    assert int(jr.depth) == ts.depth


@pytest.mark.parametrize("seed,sparse,negate", PROBLEMS[::2])
@pytest.mark.parametrize("block", [1, 3, 8, 32])
def test_bta_exact_any_block_size(seed, sparse, negate, block):
    T, u, k = _problem(seed, sparse, negate)
    nv = np.sort(np.asarray(naive_topk(jnp.asarray(T), jnp.asarray(u), k).values))
    idx = build_index(T)
    r = blocked_topk(jnp.asarray(T), idx.order_desc, idx.t_sorted_desc,
                     jnp.asarray(u), k, block_size=block)
    np.testing.assert_allclose(np.sort(np.asarray(r.values)), nv, atol=1e-4)


@pytest.mark.parametrize("seed,sparse,negate", PROBLEMS[::2])
def test_norm_pruned_exact(seed, sparse, negate):
    T, u, k = _problem(seed, sparse, negate)
    nv = np.sort(np.asarray(naive_topk(jnp.asarray(T), jnp.asarray(u), k).values))
    idx = build_index(T)
    r = norm_pruned_topk(jnp.asarray(T), idx.norm_order, idx.norms_sorted,
                         jnp.asarray(u), k, block_size=16)
    np.testing.assert_allclose(np.sort(np.asarray(r.values)), nv, atol=1e-4)


@pytest.mark.parametrize("seed", range(5))
def test_partial_ta_same_set_fewer_mults(seed):
    T, u, k = _problem(seed)
    idx = build_index(T)
    order = np.asarray(idx.order_desc)
    tv, _, ts = threshold_topk_np(T, order, u, k)
    pv, _, ps = partial_threshold_topk_np(T, order, u, k)
    np.testing.assert_allclose(np.sort(pv), np.sort(tv), atol=1e-5)
    # Alg. 3 touches the same items and never computes MORE than R terms each
    assert ps.n_items_touched == ts.n_scored
    assert ps.avg_score_fraction <= 1.0 + 1e-9


@pytest.mark.parametrize("seed", range(5))
def test_theorem4_ta_never_scores_more_than_fagin(seed):
    T, u, k = _problem(seed)
    idx = build_index(T)
    order = np.asarray(idx.order_desc)
    _, _, ts = threshold_topk_np(T, order, u, k)
    _, _, fs = fagin_topk_np(T, order, u, k)
    assert ts.n_scored <= fs.n_scored


@pytest.mark.parametrize("seed", range(5))
def test_bounds_invariants(seed):
    """LB is monotone; the loop runs iff LB < UB; the final LB is the true
    K-th best (the exactness certificate the UB trajectory must deliver)."""
    T, u, k = _problem(seed)
    idx = build_index(T)
    _, _, ts = threshold_topk_np(T, np.asarray(idx.order_desc), u, k,
                                 track_trajectory=True)
    lbs, ubs = ts.lower_bounds, ts.upper_bounds
    assert np.all(np.diff(lbs[np.isfinite(lbs)]) >= -1e-6)
    # every non-final round must have had lb < ub, else TA would have stopped
    assert np.all(lbs[:-1] < ubs[:-1] + 1e-6)
    # termination: certificate closed or lists exhausted
    assert lbs[-1] >= ubs[-1] - 1e-6 or ts.depth == T.shape[0]
    kth_best = np.sort(T @ u)[::-1][k - 1]
    np.testing.assert_allclose(lbs[-1], kth_best, atol=1e-5)


def test_batched_bta_matches_single():
    rng = np.random.default_rng(3)
    T = rng.standard_normal((300, 12)).astype(np.float32)
    U = rng.standard_normal((7, 12)).astype(np.float32)
    idx = build_index(T)
    batched = blocked_topk_batched(jnp.asarray(T), idx, jnp.asarray(U), 5,
                                   block_size=16)
    for i, u in enumerate(U):
        single = blocked_topk(jnp.asarray(T), idx.order_desc,
                              idx.t_sorted_desc, jnp.asarray(u), 5,
                              block_size=16)
        np.testing.assert_allclose(np.asarray(batched.values[i]),
                                   np.asarray(single.values), atol=1e-5)
        # liveness gating: lockstep batching must not inflate the stats of
        # queries that certified early
        assert int(batched.n_scored[i]) == int(single.n_scored)
        assert int(batched.depth[i]) == int(single.depth)


def test_halted_ta_budget_respected():
    rng = np.random.default_rng(4)
    T = rng.standard_normal((500, 20)).astype(np.float32)
    u = rng.standard_normal(20).astype(np.float32)
    idx = build_index(T)
    r = threshold_topk_from_index(jnp.asarray(T), idx, jnp.asarray(u), 5,
                                  max_rounds=3)
    assert int(r.depth) <= 3
    # halted results are a subset of scored items - values are real scores
    scores = T @ u
    ids = np.asarray(r.indices)
    ids = ids[ids >= 0]
    np.testing.assert_allclose(np.asarray(r.values)[: len(ids)], scores[ids],
                               atol=1e-4)


def test_halted_norm_pruned_budget_respected():
    """max_blocks is the uniform halting knob across every strategy."""
    rng = np.random.default_rng(5)
    T = rng.standard_normal((500, 20)).astype(np.float32)
    u = rng.standard_normal(20).astype(np.float32)
    idx = build_index(T)
    r = norm_pruned_topk(jnp.asarray(T), idx.norm_order, idx.norms_sorted,
                         jnp.asarray(u), 5, block_size=32, max_blocks=2)
    assert int(r.depth) <= 2 * 32
    scores = T @ u
    ids = np.asarray(r.indices)
    ids = ids[ids >= 0]
    np.testing.assert_allclose(np.asarray(r.values)[: len(ids)], scores[ids],
                               atol=1e-4)


# ---------------------------------------------------------------------------
# the naive executor's selection: bit-identical to lax.top_k
# ---------------------------------------------------------------------------

def _scores(kind, rng, shape):
    if kind == "ties":
        return rng.integers(-2, 3, shape).astype(np.float32)
    if kind == "neg_inf":
        x = rng.standard_normal(shape).astype(np.float32)
        x[rng.random(shape) < 0.5] = -np.inf
        return x
    if kind == "neg_inf_runs":      # whole chunks at -inf, few finite lanes
        x = np.full(shape, -np.inf, np.float32)
        live = rng.random(shape) < 0.002
        x[live] = rng.integers(-1, 2, shape)[live]
        return x
    if kind == "all_negative":
        return -np.abs(rng.standard_normal(shape)).astype(np.float32) - 1.0
    if kind == "signed_zeros":
        return rng.choice(np.array([-0.0, 0.0, -1.0], np.float32), shape)
    return rng.standard_normal(shape).astype(np.float32)


# 2 * k * 128 <= m engages the two-stage path: m = 8192 engages up to k = 32
@pytest.mark.parametrize("kind,batch,m,k,path", [
    ("ties", 3, 8192, 32, "two_stage"),
    ("ties", 1, 8192, 33, "direct"),
    ("neg_inf", 2, 4096, 4, "two_stage"),
    ("neg_inf_runs", 1, 16384, 16, "two_stage"),
    ("all_negative", 1, 8192, 32, "two_stage"),
    ("all_negative", 4, 8192, 33, "direct"),
    ("signed_zeros", 2, 8192, 5, "two_stage"),
    ("normal", None, 8192, 8, "two_stage"),
    ("normal", 2, 8200, 1, "direct"),
])
def test_select_topk_is_lax_top_k(kind, batch, m, k, path):
    """Same float bits and the same ids as ``lax.top_k`` (ties to the
    lower id), on both sides of the engagement rule."""
    assert select_path(m, k) == path
    shape = (m,) if batch is None else (batch, m)
    sel = jax.jit(select_topk, static_argnums=1)
    ref = jax.jit(jax.lax.top_k, static_argnums=1)
    for seed in range(4):
        x = jnp.asarray(_scores(kind, np.random.default_rng(seed), shape))
        v, i = sel(x, k)
        rv, ri = ref(x, k)
        np.testing.assert_array_equal(np.asarray(v).view(np.int32),
                                      np.asarray(rv).view(np.int32))
        np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
