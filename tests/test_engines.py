"""Engine-layer tests: the registry, the unified driver, and the strategies.

The acceptance sweep runs EVERY registered exact engine (including the
Pallas backend in interpret mode) against ``naive_topk`` on random,
sparse, and negative-weight queries — new engines registered later are
covered automatically.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    EngineContext,
    batch_bucket,
    blocked_topk,
    chunked_ta_topk,
    engine_names,
    get_engine,
    list_engines,
    merge_topk_sorted,
    naive_topk,
    norm_pruned_topk,
    pruned_block_scan,
    select_engine,
    ta_round_strategy,
    threshold_topk_np,
)
from repro.core.index import build_index
from repro.core.strategies import blocked_lists_strategy, norm_block_strategy


def _queries(rng, b, r):
    """Random, sparse (mostly-zero), and mixed-sign/negative queries."""
    dense = rng.standard_normal((b, r)).astype(np.float32)
    sparse = dense.copy()
    sparse[rng.random((b, r)) < 0.7] = 0.0
    sparse[np.all(sparse == 0, axis=1), 0] = 1.0
    mixed = dense.copy()
    mixed[:, ::2] *= -1.0
    negative = -np.abs(dense)
    return {"random": dense, "sparse": sparse, "mixed_sign": mixed,
            "negative": negative}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


def test_registry_contents_and_metadata():
    names = engine_names()
    for expected in ("naive", "ta", "bta", "norm", "norm_sharded",
                     "pallas", "fagin", "partial", "auto"):
        assert expected in names
    assert not get_engine("naive").needs_index
    assert get_engine("pallas").backend == "pallas"
    # layout declarations (DESIGN.md §7)
    assert get_engine("ta").layout == "list_major"
    assert get_engine("bta").layout == "list_major"
    assert get_engine("norm").layout == "norm_major"
    assert get_engine("norm_sharded").layout == "norm_sharded"
    # host-only reference oracles: exact, numpy backend, never jitted
    for oracle in ("fagin", "partial"):
        e = get_engine(oracle)
        assert e.exact and e.host_only and e.backend == "numpy"
        assert e.make_batched is None and e.dispatch is not None
    # aliases resolve to canonical engines
    assert get_engine("threshold").name == "ta"
    assert get_engine("blocked").name == "bta"
    assert get_engine("norm_pruned").name == "norm"
    assert get_engine("topk_mips").name == "pallas"


def test_registry_unknown_engine_raises():
    with pytest.raises(ValueError, match="unknown engine"):
        get_engine("definitely_not_an_engine")


def test_list_engines_filters():
    assert all(e.exact for e in list_engines(exact=True))
    pallas = list_engines(backend="pallas")
    assert [e.name for e in pallas] == ["pallas"]
    assert all(not e.needs_index for e in list_engines(needs_index=False))


# ---------------------------------------------------------------------------
# Acceptance sweep: every exact engine vs naive on all query regimes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,r,k", [(37, 8, 5), (256, 16, 1), (300, 12, 10)])
def test_every_exact_engine_matches_naive(m, r, k):
    rng = np.random.default_rng(m * r + k)
    T = rng.standard_normal((m, r)).astype(np.float32)
    ctx = EngineContext(T, block_size=16)
    for regime, U in _queries(rng, 4, r).items():
        Uj = jnp.asarray(U)
        ref = np.sort(np.asarray(naive_topk(ctx.targets, Uj, k).values),
                      axis=1)
        for eng in list_engines(exact=True):
            res = eng.run(ctx, Uj, k)
            np.testing.assert_allclose(
                np.sort(np.asarray(res.values), axis=1), ref, atol=1e-3,
                err_msg=f"engine={eng.name} regime={regime}")


def test_engine_ids_are_valid_catalogue_ids():
    rng = np.random.default_rng(11)
    T = rng.standard_normal((123, 9)).astype(np.float32)
    ctx = EngineContext(T, block_size=16)
    U = jnp.asarray(rng.standard_normal((3, 9)).astype(np.float32))
    for eng in list_engines(exact=True):
        res = eng.run(ctx, U, 5)
        ids = np.asarray(res.indices)
        vals = np.asarray(res.values)
        scores = np.asarray(U) @ T.T
        for b in range(ids.shape[0]):
            np.testing.assert_allclose(scores[b, ids[b]], vals[b], atol=1e-3,
                                       err_msg=eng.name)


@pytest.mark.parametrize("sign", ["mixed", "negative"])
@pytest.mark.parametrize("m_real,bucket", [
    (2 ** 13 - 1, None), (2 ** 13, None), (2 ** 13 + 1, None), (3, 2 ** 13)])
def test_naive_two_stage_selection_is_exact(m_real, bucket, sign):
    """The naive engine at shapes that engage the two-stage selection
    (k = 5 over an M-bucket of 8,192 or 16,384): small-integer factors
    make every score exact in float32 and tie heavily, so the ids must
    be the float64 reference's (ties to the lower id), pad rows never
    surface, and ``-1`` marks exactly the ``-inf`` slots."""
    from repro.core.naive import select_path

    rng = np.random.default_rng(m_real)
    T = rng.integers(-2, 3, (m_real, 6)).astype(np.float32)
    U = rng.integers(-2, 3, (3, 6)).astype(np.float32)
    if sign == "negative":      # every real score below zero
        T, U = np.abs(T) + 1.0, -np.abs(U) - 1.0
    k = 5
    ctx = EngineContext(T)
    eng = get_engine("naive")
    args = ctx.engine_args(eng, bucket=bucket, cache=False)
    assert select_path(args["targets"].shape[0], k) == "two_stage"
    res = ctx._dispatch_args(eng, args, jnp.asarray(U), k)
    vals, ids = np.asarray(res.values), np.asarray(res.indices)

    scores = U.astype(np.float64) @ T.T.astype(np.float64)
    want = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    n = min(k, m_real)
    np.testing.assert_array_equal(ids[:, :n], want[:, :n])
    np.testing.assert_array_equal(
        vals[:, :n], np.take_along_axis(scores, want[:, :n], 1))
    assert np.all(ids < m_real)
    np.testing.assert_array_equal(ids == -1, np.isneginf(vals))
    assert np.all(np.isneginf(vals[:, n:]))


# ---------------------------------------------------------------------------
# auto policy
# ---------------------------------------------------------------------------


def test_auto_selects_ta_for_sparse_batches():
    rng = np.random.default_rng(0)
    ctx = EngineContext(rng.standard_normal((500, 24)).astype(np.float32))
    U = np.zeros((4, 24), np.float32)
    U[:, :3] = 1.0
    assert select_engine(ctx, jnp.asarray(U)).name == "ta"


def test_auto_selects_norm_backend_for_decaying_catalogues():
    rng = np.random.default_rng(1)
    T = rng.standard_normal((2000, 16)).astype(np.float32)
    T *= (1.0 / np.sqrt(1.0 + np.arange(2000)))[:, None]
    ctx = EngineContext(T)
    U = jnp.asarray(rng.standard_normal((4, 16)).astype(np.float32))
    assert select_engine(ctx, U).name == "norm"


def test_auto_selects_bta_for_dense_flat_catalogues():
    # B-aware policy (DESIGN.md §11): BTA needs BOTH a flat spectrum and
    # a batch big enough to amortise the batched-native list scan
    rng = np.random.default_rng(2)
    ctx = EngineContext(rng.standard_normal((1000, 16)).astype(np.float32),
                        prefix_depth=64)
    U = jnp.asarray(rng.standard_normal((8, 16)).astype(np.float32))
    assert select_engine(ctx, U).name == "bta"
    # below the amortisation threshold the shared-tile norm scan wins
    assert select_engine(ctx, U[:2]).name == "norm"
    # with the list layout off there is no batched path at any B: the
    # per-query list loop never beats the contiguous norm scan
    ctx_off = EngineContext(
        rng.standard_normal((1000, 16)).astype(np.float32), prefix_depth=0)
    assert select_engine(ctx_off, U).name == "norm"


def test_auto_sparse_small_batch_avoids_lockstep_list_scan():
    # sparse queries still pick TA when the batched path is live (B >= 8)
    # or when the layout is off (cache-resident gather path); a SMALL
    # batch with the layout on would pay the per-query lockstep loop, so
    # the policy falls through to the norm scan
    rng = np.random.default_rng(3)
    U = np.zeros((8, 24), np.float32)
    U[:, :3] = 1.0
    ctx = EngineContext(rng.standard_normal((500, 24)).astype(np.float32),
                        prefix_depth=64)
    assert select_engine(ctx, jnp.asarray(U)).name == "ta"
    assert select_engine(ctx, jnp.asarray(U[:2])).name == "norm"


# ---------------------------------------------------------------------------
# Blocked path: mixed-sign and mostly-zero queries vs the numpy oracle
# (the gather-side list flip previously had no direct coverage)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block", [1, 7, 32])
@pytest.mark.parametrize("regime", ["mixed_sign", "sparse", "negative"])
def test_blocked_flip_and_sparse_match_oracle(block, regime):
    rng = np.random.default_rng(17)
    T = rng.standard_normal((150, 10)).astype(np.float32)
    idx = build_index(T)
    for u in _queries(rng, 3, 10)[regime]:
        ov, _, ostats = threshold_topk_np(T, np.asarray(idx.order_desc), u, 4)
        r = blocked_topk(jnp.asarray(T), idx.order_desc, idx.t_sorted_desc,
                         jnp.asarray(u), 4, block_size=block)
        np.testing.assert_allclose(np.sort(np.asarray(r.values)),
                                   np.sort(ov), atol=1e-4)
        if block == 1:
            # block_size=1 IS the paper's TA round structure, count-for-count
            assert int(r.n_scored) == ostats.n_scored
            assert int(r.depth) == ostats.depth


def test_driver_direct_strategies_agree():
    """The three strategies, run straight through pruned_block_scan."""
    rng = np.random.default_rng(23)
    T = rng.standard_normal((90, 7)).astype(np.float32)
    u = rng.standard_normal(7).astype(np.float32)
    u[2] = 0.0
    u[3] *= -1.0
    idx = build_index(T)
    Tj, uj = jnp.asarray(T), jnp.asarray(u)
    ref = np.sort(np.asarray(naive_topk(Tj, uj, 5).values))
    order, t_sorted, _ = idx.query_views(uj)   # desc arrays + flags
    for strat in (
        ta_round_strategy(order, t_sorted, uj),
        blocked_lists_strategy(idx.order_desc, idx.t_sorted_desc, uj, 8),
        norm_block_strategy(idx.norm_order, idx.norms_sorted, uj, 8),
    ):
        res = pruned_block_scan(Tj, uj, strat, 5)
        np.testing.assert_allclose(np.sort(np.asarray(res.values)), ref,
                                   atol=1e-4)


def test_driver_uniform_halting():
    """max_steps caps every strategy through the same driver argument."""
    rng = np.random.default_rng(29)
    T = rng.standard_normal((400, 12)).astype(np.float32)
    u = rng.standard_normal(12).astype(np.float32)
    idx = build_index(T)
    Tj, uj = jnp.asarray(T), jnp.asarray(u)
    order, t_sorted, _ = idx.query_views(uj)
    for strat in (
        ta_round_strategy(order, t_sorted, uj),
        blocked_lists_strategy(idx.order_desc, idx.t_sorted_desc, uj, 16),
        norm_block_strategy(idx.norm_order, idx.norms_sorted, uj, 16),
    ):
        res = pruned_block_scan(Tj, uj, strat, 5, max_steps=3)
        assert int(res.depth) <= 3


def _tied_problem(rng, m=200, r=8, b=5):
    """Integer-valued catalogue/queries: exact score ties, exact float32
    arithmetic — the adversarial regime for count-faithful stopping."""
    T = rng.integers(-3, 4, (m, r)).astype(np.float32)
    U = rng.integers(-2, 3, (b, r)).astype(np.float32)
    U[np.all(U == 0, axis=1), 0] = 1.0
    return T, U


# ---------------------------------------------------------------------------
# Chunked TA: exactness + n_scored/depth equality vs the sequential oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("chunk", [1, 4, 16, 64])
@pytest.mark.parametrize("regime", ["mixed_sign", "sparse", "random"])
def test_chunked_ta_counts_match_sequential_oracle(chunk, regime):
    rng = np.random.default_rng(41)
    T = rng.standard_normal((180, 12)).astype(np.float32)
    idx = build_index(T)
    for u in _queries(rng, 4, 12)[regime]:
        ov, _, ostats = threshold_topk_np(T, np.asarray(idx.order_desc), u, 6)
        r = chunked_ta_topk(jnp.asarray(T), idx.order_desc,
                            idx.t_sorted_desc, idx.rank_desc,
                            jnp.asarray(u), 6, chunk=chunk)
        np.testing.assert_allclose(np.sort(np.asarray(r.values)),
                                   np.sort(ov), atol=1e-4)
        assert int(r.n_scored) == ostats.n_scored, (chunk, regime)
        assert int(r.depth) == ostats.depth, (chunk, regime)


@pytest.mark.parametrize("chunk", [1, 8, 32])
def test_chunked_ta_counts_on_tied_scores(chunk):
    rng = np.random.default_rng(43)
    T, U = _tied_problem(rng)
    idx = build_index(T)
    for u in U:
        ov, _, ostats = threshold_topk_np(T, np.asarray(idx.order_desc), u, 5)
        r = chunked_ta_topk(jnp.asarray(T), idx.order_desc,
                            idx.t_sorted_desc, idx.rank_desc,
                            jnp.asarray(u), 5, chunk=chunk)
        # integer data: arithmetic is exact, so equality is exact too
        np.testing.assert_array_equal(np.sort(np.asarray(r.values)),
                                      np.sort(ov).astype(np.float32))
        assert int(r.n_scored) == ostats.n_scored, chunk
        assert int(r.depth) == ostats.depth, chunk


def test_chunked_ta_halted_budget_is_round_granular():
    rng = np.random.default_rng(47)
    T = rng.standard_normal((300, 10)).astype(np.float32)
    idx = build_index(T)
    u = jnp.asarray(rng.standard_normal(10).astype(np.float32))
    for chunk in (1, 8, 32):
        r = chunked_ta_topk(jnp.asarray(T), idx.order_desc,
                            idx.t_sorted_desc, idx.rank_desc, u, 5,
                            chunk=chunk, max_rounds=11)
        assert int(r.depth) <= 11, chunk


# ---------------------------------------------------------------------------
# Compile cache: repeated same-shape queries must not retrace
# ---------------------------------------------------------------------------


def test_repeated_same_shape_calls_do_not_retrace():
    rng = np.random.default_rng(53)
    # shapes unique to this test (R=21, k=6): under the MODULE-LEVEL
    # argument-passing executors (DESIGN.md §10) the trace cache is
    # process-wide, so a signature another test already compiled would
    # legitimately attribute 0 traces to this context
    T = rng.standard_normal((600, 21)).astype(np.float32)
    ctx = EngineContext(T, block_size=64)
    U = jnp.asarray(rng.standard_normal((4, 21)).astype(np.float32))
    # host-only oracles never trace; dispatch engines have no executable
    engines = [e for e in list_engines() if e.has_executable]
    for eng in engines:
        eng.run(ctx, U, 6)                   # populates the cache
    warm = dict(ctx.trace_counts)
    assert all(warm.get(e.name, 0) >= 1 for e in engines)
    for _ in range(3):
        for eng in engines:
            eng.run(ctx, U, 6)
    assert ctx.trace_counts == warm          # 0 new traces after warmup
    # a second norm call specifically must not rebuild its executable
    before = ctx.trace_counts["norm"]
    get_engine("norm").run(ctx, U, 6)
    assert ctx.trace_counts["norm"] == before
    # and a SECOND context of the same M-bucket shares every trace: the
    # argument-passing engines attribute nothing to it (pallas, the one
    # closure engine, still compiles per context)
    ctx2 = EngineContext(
        rng.standard_normal((555, 21)).astype(np.float32), block_size=64)
    for eng in engines:
        if eng.run_args is not None:
            eng.run(ctx2, U, 6)
    assert ctx2.trace_counts == {}


def test_batch_bucketing_pads_and_slices():
    assert [batch_bucket(n) for n in (1, 2, 3, 5, 8, 9, 64)] == \
        [1, 2, 4, 8, 8, 16, 64]
    rng = np.random.default_rng(59)
    T = rng.standard_normal((400, 12)).astype(np.float32)
    ctx = EngineContext(T, block_size=32)
    U = jnp.asarray(rng.standard_normal((5, 12)).astype(np.float32))
    ref = np.sort(np.asarray(naive_topk(ctx.targets, U, 4).values), axis=1)
    for eng in list_engines(exact=True):
        res = eng.run(ctx, U, 4)             # 5 -> bucket 8 -> sliced to 5
        assert np.asarray(res.values).shape == (5, 4)
        np.testing.assert_allclose(np.sort(np.asarray(res.values), axis=1),
                                   ref, atol=1e-3, err_msg=eng.name)
    # buckets compile once: batch 5 and 7 share the bucket-8 executable
    warm = dict(ctx.trace_counts)
    U7 = jnp.asarray(rng.standard_normal((7, 12)).astype(np.float32))
    for eng in list_engines(exact=True):
        eng.run(ctx, U7, 4)
    assert ctx.trace_counts == warm


def test_context_warmup_precompiles():
    rng = np.random.default_rng(61)
    ctx = EngineContext(rng.standard_normal((300, 8)).astype(np.float32),
                        block_size=32)
    ctx.warmup(3, batch_sizes=(2,), engines=["norm", "bta"])
    warm = dict(ctx.trace_counts)
    U = jnp.asarray(rng.standard_normal((2, 8)).astype(np.float32))
    get_engine("norm").run(ctx, U, 3)
    get_engine("bta").run(ctx, U, 3)
    assert ctx.trace_counts == warm


# ---------------------------------------------------------------------------
# Merge network invariants (DESIGN.md §6): both inputs sorted descending
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(6))
def test_merge_topk_sorted_matches_full_sort(seed):
    rng = np.random.default_rng(seed)
    k = int(rng.integers(1, 12))
    a = np.sort(rng.standard_normal(k).astype(np.float32))[::-1].copy()
    b = np.sort(rng.standard_normal(k).astype(np.float32))[::-1].copy()
    if seed % 2:
        a[: k // 2] = float("-inf")      # partially-filled carry
    av, ai = jnp.asarray(a), jnp.arange(k, dtype=jnp.int32)
    bv, bi = jnp.asarray(b), jnp.arange(k, 2 * k, dtype=jnp.int32)
    ov, oi = merge_topk_sorted(av, ai, bv, bi, k)
    ref = np.sort(np.concatenate([a, b]))[::-1][:k]
    np.testing.assert_allclose(np.asarray(ov), ref, atol=0)
    assert np.asarray(oi).shape == (k,)


def test_merge_topk_sorted_ties_prefer_carry():
    av = jnp.asarray(np.float32([5.0, 3.0, 1.0]))
    bv = jnp.asarray(np.float32([5.0, 3.0, 2.0]))
    ai = jnp.asarray(np.int32([10, 11, 12]))
    bi = jnp.asarray(np.int32([20, 21, 22]))
    ov, oi = merge_topk_sorted(av, ai, bv, bi, 3)
    np.testing.assert_allclose(np.asarray(ov), [5.0, 5.0, 3.0])
    assert list(np.asarray(oi)) == [10, 20, 11]   # carry id first on ties


# ---------------------------------------------------------------------------
# The off-CPU branch, run on the CPU: ``jax.default_backend`` patched to
# "tpu" makes the driver trace its merge network (core/driver.py)
# ---------------------------------------------------------------------------


@pytest.fixture
def tpu_backend(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.mark.parametrize("ka,kb,k,case", [
    (6, 6, 6, "random"), (5, 5, 5, "ties"), (6, 6, 4, "neg_inf_pads"),
    (3, 3, 5, "k_above_both"), (8, 2, 8, "short_block"),
])
def test_merge_network_equals_concat_top_k(ka, kb, k, case, monkeypatch):
    rng = np.random.default_rng(ka * 10 + k)
    a = rng.standard_normal(ka).astype(np.float32)
    b = rng.standard_normal(kb).astype(np.float32)
    if case == "ties":
        a, b = np.float32([4, 2, 2, 1, 0]), np.float32([4, 2, 1, 1, -1])
    if case == "neg_inf_pads":
        a[ka // 2:] = -np.inf
        b[1:] = -np.inf
    a, b = np.sort(a)[::-1].copy(), np.sort(b)[::-1].copy()
    args = (jnp.asarray(a), jnp.arange(ka, dtype=jnp.int32),
            jnp.asarray(b), jnp.arange(100, 100 + kb, dtype=jnp.int32), k)
    cv, ci = merge_topk_sorted(*args)                 # concat + top_k
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    nv, ni = merge_topk_sorted(*args)                 # the merge network
    np.testing.assert_array_equal(np.asarray(nv), np.asarray(cv))
    np.testing.assert_array_equal(np.asarray(ni), np.asarray(ci))


@pytest.mark.parametrize("sign", ["mixed", "nonneg"])
@pytest.mark.parametrize("m", [2 ** 11 - 1, 2 ** 11 + 1])
def test_engines_on_merge_network_branch_equal_naive(m, sign, tpu_backend):
    """norm/bta/ta traced on the off-CPU branch, at HIGHEST scoring
    precision, against a float64 dense reference: the engines' f32 bounds
    and their scores agree, so the answers are exact."""
    from repro.core.engines import trace_totals
    # shapes no other test traces, so every engine traces on the branch
    r, k = 11, {"mixed": 7, "nonneg": 6}[sign]
    rng = np.random.default_rng(m)
    T = rng.standard_normal((m, r)).astype(np.float32)
    T *= (1.0 / np.sqrt(1.0 + np.arange(r)))[None, :].astype(np.float32)
    U = rng.standard_normal((5, r)).astype(np.float32)
    if sign == "nonneg":
        U = np.abs(U)
    ref = np.sort(U.astype(np.float64) @ T.astype(np.float64).T,
                  axis=1)[:, ::-1][:, :k]
    ctx = EngineContext(T, block_size=16, prefix_depth=64)
    for name in ("norm", "bta", "ta"):
        before = trace_totals().get(name, 0)
        res = get_engine(name).run(ctx, jnp.asarray(U), k)
        assert trace_totals().get(name, 0) > before, \
            f"{name}: a cached CPU-branch trace ran, not the network"
        np.testing.assert_allclose(np.asarray(res.values), ref,
                                   rtol=1e-6, atol=1e-6, err_msg=name)
        ids = np.asarray(res.indices)
        own = np.take_along_axis(U @ T.T, ids, axis=1)
        np.testing.assert_allclose(own, np.asarray(res.values),
                                   rtol=1e-6, atol=1e-6, err_msg=name)


def test_pallas_is_refused_and_never_routed_on_tpu(tpu_backend):
    from repro.core.engines import (PALLAS_TPU_REFUSAL, auto_candidates,
                                    executable_engines)
    assert "pallas" not in auto_candidates()
    assert "pallas" not in executable_engines()
    assert "norm" in executable_engines()
    rng = np.random.default_rng(5)
    T = rng.standard_normal((300, 8)).astype(np.float32)
    T *= (1.0 / np.sqrt(1.0 + np.arange(300)))[:, None].astype(np.float32)
    ctx = EngineContext(T)
    U = jnp.asarray(rng.standard_normal((2, 8)).astype(np.float32))
    assert select_engine(ctx, U).name == "norm"
    with pytest.raises(ValueError, match="does not run on a TPU backend"):
        get_engine("pallas").run(ctx, U, 3)
    assert "top_k" in PALLAS_TPU_REFUSAL and "8 and 128" in PALLAS_TPU_REFUSAL


def test_pallas_engine_counts_are_block_granular():
    rng = np.random.default_rng(31)
    T = rng.standard_normal((512, 16)).astype(np.float32)
    T *= (1.0 / (1.0 + np.arange(512)))[:, None] ** 0.5
    ctx = EngineContext(T, block_size=64)
    U = jnp.asarray(rng.standard_normal((3, 16)).astype(np.float32))
    res = get_engine("pallas").run(ctx, U, 5)
    n = np.asarray(res.n_scored)
    assert np.all(n % 64 == 0)
    assert np.all(n < 512)          # the decaying catalogue prunes blocks


# ---------------------------------------------------------------------------
# Host-only reference oracles as registry engines (fagin / partial)
# ---------------------------------------------------------------------------


def test_fagin_engine_matches_ta_values():
    rng = np.random.default_rng(71)
    T = rng.standard_normal((140, 9)).astype(np.float32)
    ctx = EngineContext(T, block_size=16)
    for regime, U in _queries(rng, 3, 9).items():
        Uj = jnp.asarray(U)
        r_ta = get_engine("ta").run(ctx, Uj, 6)
        r_f = get_engine("fagin").run(ctx, Uj, 6)
        np.testing.assert_allclose(
            np.sort(np.asarray(r_f.values), axis=1),
            np.sort(np.asarray(r_ta.values), axis=1), atol=1e-4,
            err_msg=regime)


def test_partial_engine_item_counts_equal_ta():
    """Theorem 4 logic: partial TA touches exactly TA's item set, so its
    n_scored (items touched) equals the ta engine's count-faithful
    n_scored query for query."""
    rng = np.random.default_rng(73)
    T = rng.standard_normal((160, 8)).astype(np.float32)
    ctx = EngineContext(T, block_size=16)
    for regime, U in _queries(rng, 3, 8).items():
        Uj = jnp.asarray(U)
        r_ta = get_engine("ta").run(ctx, Uj, 5)
        r_p = get_engine("partial").run(ctx, Uj, 5)
        np.testing.assert_allclose(
            np.sort(np.asarray(r_p.values), axis=1),
            np.sort(np.asarray(r_ta.values), axis=1), atol=1e-4,
            err_msg=regime)
        np.testing.assert_array_equal(
            np.asarray(r_p.n_scored), np.asarray(r_ta.n_scored),
            err_msg=regime)


# ---------------------------------------------------------------------------
# CostTable persistence (ROADMAP 2b): a restarted server routes by
# measured costs before any observation, across snapshot swaps
# ---------------------------------------------------------------------------


def test_cost_table_save_load_roundtrip(tmp_path):
    from repro.core import CostTable

    t = CostTable(alpha=0.3)
    t.observe("norm", 1, "", 2e-4)
    t.observe("norm", 1, "", 1e-4)        # EWMA folds, not overwrites
    t.observe("ta", 64, "POS:5", 3e-4)
    path = tmp_path / "costs.json"
    t.save(path)
    t2 = CostTable.load(path)
    assert t2.alpha == t.alpha
    assert t2.n_observations == t.n_observations == 3
    assert t2.snapshot() == t.snapshot()
    assert t2.predict("ta", 64, "POS:5") == t.predict("ta", 64, "POS:5")
    assert t2.engine_cost("norm") == t.engine_cost("norm")
    # loaded EWMAs are live priors: new observations keep folding in
    before = t2.predict("norm", 1, "")
    t2.observe("norm", 1, "", 9e-4)
    assert t2.predict("norm", 1, "") != before


def test_loaded_cost_table_routes_before_any_measurement(tmp_path):
    """The restart story: a table measured in a previous process routes
    the auto policy from disk BEFORE this process observes anything —
    and keeps routing after a compaction swaps the snapshot (every
    compaction-built context shares the one table instance)."""
    from repro.core import CostTable, SepLRModel
    from repro.core.engines import auto_candidates, cost_label
    from repro.serving.server import TopKServer

    rng = np.random.default_rng(91)
    T = rng.standard_normal((120, 8)).astype(np.float32)
    U = rng.standard_normal((1, 8)).astype(np.float32)
    probe = EngineContext(T, block_size=16)
    # "previous process": granular measurements for every auto candidate
    # at this batch's (bucket, sign) — ta measured cheapest, which the
    # cold heuristic would never pick for a dense B=1 batch
    prev = CostTable()
    for i, name in enumerate(auto_candidates()):
        lbl = cost_label(get_engine(name), probe, U)
        cost = 1e-5 if name == "ta" else (i + 2) * 1e-3
        prev.observe(name, batch_bucket(1), lbl, cost)
    path = tmp_path / "costs.json"
    prev.save(path)

    loaded = CostTable.load(path)
    srv = TopKServer(SepLRModel(T), block_size=16, delta_capacity=8,
                     cost_table=loaded)
    assert srv.cost_table is loaded
    assert loaded.n_observations == len(auto_candidates())
    picked = select_engine(srv.ctx, U)
    assert picked.name == "ta"            # measured route, not heuristic
    # ...and the measurements survive a snapshot swap: the compaction
    # builds a NEW context around the SAME shared table
    v0 = srv.catalogue.version
    srv.add_targets(rng.standard_normal((9, 8)).astype(np.float32))
    srv.catalogue.compact(wait=True)
    assert srv.catalogue.version > v0
    assert srv.ctx.cost_table is loaded
    assert select_engine(srv.ctx, U).name == "ta"
