"""Randomized differential harness for the streaming LSM ladder (§15).

Interleaved insert / update / delete / query / compact / fault-injection
schedules run against :class:`ShardedLsmCatalogue` (and, as the
n_shards=0 arm, the single-level :class:`SegmentedCatalogue`) and EVERY
query is checked against a fresh-rebuild oracle: an independent
``{gid: row}`` shadow dict scored in float64. The ladder may be in any
internal state — active delta, sealed L0 chain (including chains
retained by injected fold/build failures), per-shard L1 runs,
mid-promotion — and the answers must still be exactly the dense top-K.

Two drivers share one replay core:

* a seeded numpy schedule sweep that always runs —
  ``STREAMING_SCHEDULES=200`` (default 30) reproduces the acceptance
  sweep with no third-party dependency; every schedule prints its
  repro seed on failure;
* hypothesis properties (when the library is installed) that add
  minimised counterexamples on top. ``HYPOTHESIS_PROFILE=ci`` runs a
  bounded-example smoke, ``full`` the 200-schedule sweep (100 examples
  x 2 properties), the default sits in between. Shrunk failures replay
  from the ``note()``-printed draw, independent of the profile that
  found them.
"""

import os

import numpy as np
import pytest

from repro.core import (
    SegmentedCatalogue,
    ShardedLsmCatalogue,
    faults,
    get_engine,
)

try:
    from hypothesis import HealthCheck, given, note, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                  # pragma: no cover
    HAVE_HYPOTHESIS = False

R = 6
K = 4

# boundary row counts the delta/block quantisation is most likely to
# mis-handle: 2^n - 1, 2^n, 2^n + 1
BOUNDARY_M = [7, 8, 9, 15, 16, 17, 31, 32, 33]
SHARD_COUNTS = [0, 1, 4, 8]          # 0 = single-level SegmentedCatalogue

_KINDS = ["insert", "delete", "update", "query", "compact", "flush",
          "fault_build", "fault_fold"]
_WEIGHTS = [0.30, 0.12, 0.12, 0.18, 0.10, 0.06, 0.06, 0.06]


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.disarm_all()
    yield
    faults.disarm_all()


def _rows(rng, n, positive):
    r = rng.standard_normal((n, R)).astype(np.float32)
    return np.abs(r) if positive else r


def _make(base, n_shards, compact_async):
    kw = dict(delta_capacity=4, block_size=8, compact_async=compact_async,
              build_backoff_s=0.0, max_l0_segments=8)
    if n_shards == 0:
        return SegmentedCatalogue(base, **kw)
    return ShardedLsmCatalogue(base, n_shards=n_shards, l1_capacity=8, **kw)


def _check_query(cat, shadow, U, k=K, engine="norm"):
    """One query vs the fresh-rebuild oracle: exact values, live +
    consistent gids, correct padding."""
    res, _ = cat.query(get_engine(engine), U, k)
    vals = np.asarray(res.values)
    idx = np.asarray(res.indices)
    assert cat.num_live == len(shadow)
    kk = min(k, len(shadow))
    if kk == 0:
        assert np.all(idx == -1)
        return
    gids = np.fromiter(shadow.keys(), np.int64, len(shadow))
    rows = np.stack([shadow[int(g)] for g in gids]).astype(np.float64)
    s = np.atleast_2d(U).astype(np.float64) @ rows.T
    want = -np.sort(-s, axis=1)[:, :kk]
    np.testing.assert_allclose(vals[:, :kk], want, atol=1e-4)
    # every returned gid is live and scores to the value next to it
    by_gid = {int(g): rows[i] for i, g in enumerate(gids)}
    for b in range(idx.shape[0]):
        for j in range(kk):
            g = int(idx[b, j])
            assert g in by_gid, (b, j, g)
            np.testing.assert_allclose(
                vals[b, j],
                float(np.atleast_2d(U).astype(np.float64)[b] @ by_gid[g]),
                atol=1e-4)
    assert np.all(idx[:, kk:] == -1)


def _replay(cat, shadow, ops, rng, positive, *, faultable=True):
    """Apply one schedule to (catalogue, shadow) in lockstep, checking
    exactness at every query op and once more at the end."""
    for op in ops:
        kind = op[0]
        if kind == "insert":
            rows = _rows(rng, op[1], positive)
            for g, row in zip(cat.add_targets(rows), rows):
                shadow[int(g)] = row
        elif kind == "delete":
            if shadow:
                victim = sorted(shadow)[op[1] % len(shadow)]
                cat.delete_targets([victim])
                del shadow[victim]
        elif kind == "update":
            if shadow:
                victim = sorted(shadow)[op[1] % len(shadow)]
                row = _rows(rng, 1, positive)
                cat.update_targets([victim], row)
                shadow[victim] = row[0]
        elif kind == "query":
            _check_query(cat, shadow, _rows(rng, op[1], positive))
        elif kind == "compact":
            try:
                cat.compact(wait=True)
            except RuntimeError:
                pass                     # injected failure: chain retained
        elif kind == "flush":
            cat.flush()
        elif kind == "fault_build" and faultable:
            faults.arm("compaction.build", error=RuntimeError, times=1)
        elif kind == "fault_fold" and faultable:
            faults.arm("compaction.fold_l1", error=RuntimeError, times=1)
    _check_query(cat, shadow, _rows(rng, 2, positive))


def _draw_schedule(rng, *, faultable=True):
    ops = []
    for _ in range(int(rng.integers(1, 25))):
        kind = rng.choice(_KINDS, p=_WEIGHTS)
        if not faultable and kind.startswith("fault"):
            kind = "compact"
        if kind == "insert":
            ops.append(("insert", int(rng.integers(1, 7))))
        elif kind in ("delete", "update"):
            ops.append((kind, int(rng.integers(0, 64))))
        elif kind == "query":
            ops.append(("query", int(rng.integers(1, 3))))
        else:
            ops.append((kind,))
    return ops


def _run_one_schedule(seed):
    """One fully seed-determined schedule: catalogue shape, op stream
    and data all derive from ``seed``."""
    rng = np.random.default_rng(seed)
    n_shards = SHARD_COUNTS[int(rng.integers(len(SHARD_COUNTS)))]
    m0 = BOUNDARY_M[int(rng.integers(len(BOUNDARY_M)))]
    positive = bool(rng.integers(2))
    compact_async = bool(rng.integers(2))
    ops = _draw_schedule(rng)
    base = _rows(rng, m0, positive)
    cat = _make(base, n_shards, compact_async)
    shadow = {i: base[i] for i in range(m0)}
    try:
        _replay(cat, shadow, ops, rng, positive)
    finally:
        faults.disarm_all()
        cat.flush()


def test_seeded_schedule_sweep():
    """The dependency-free sweep: STREAMING_SCHEDULES independent
    schedules (acceptance: 200), each reproducible from the printed
    seed alone via ``_run_one_schedule(seed)``."""
    n = int(os.environ.get("STREAMING_SCHEDULES", "30"))
    for seed in range(n):
        try:
            _run_one_schedule(seed)
        except Exception:
            print(f"streaming schedule FAILED: "
                  f"_run_one_schedule({seed}) reproduces it")
            raise


if HAVE_HYPOTHESIS:
    settings.register_profile(
        "ci", max_examples=20, deadline=None,
        suppress_health_check=[HealthCheck.too_slow,
                               HealthCheck.data_too_large,
                               HealthCheck.filter_too_much])
    settings.register_profile(
        "default", max_examples=40, deadline=None,
        suppress_health_check=[HealthCheck.too_slow,
                               HealthCheck.data_too_large,
                               HealthCheck.filter_too_much])
    settings.register_profile(
        "full", max_examples=100, deadline=None,
        suppress_health_check=[HealthCheck.too_slow,
                               HealthCheck.data_too_large,
                               HealthCheck.filter_too_much])
    settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "default"))

    _OPS = st.lists(
        st.one_of(
            st.tuples(st.just("insert"), st.integers(1, 6)),
            st.tuples(st.just("delete"), st.integers(0, 63)),
            st.tuples(st.just("update"), st.integers(0, 63)),
            st.tuples(st.just("query"), st.integers(1, 2)),
            st.tuples(st.just("compact")),
            st.tuples(st.just("flush")),
            st.tuples(st.just("fault_build")),
            st.tuples(st.just("fault_fold")),
        ),
        min_size=1, max_size=24)

    # the fault-free subset (for the two-catalogue differential, where
    # an injected failure would just make both arms take the same detour)
    _CLEAN_OPS = st.lists(
        st.one_of(
            st.tuples(st.just("insert"), st.integers(1, 6)),
            st.tuples(st.just("delete"), st.integers(0, 63)),
            st.tuples(st.just("update"), st.integers(0, 63)),
            st.tuples(st.just("query"), st.integers(1, 2)),
            st.tuples(st.just("compact")),
            st.tuples(st.just("flush")),
        ),
        min_size=1, max_size=24)

    @given(data=st.data())
    def test_interleaved_schedules_match_fresh_rebuild_oracle(data):
        """The headline property: ANY interleaving of mutations,
        queries, compactions and injected fold/build failures, over any
        shard count and boundary base size, answers every query
        exactly."""
        n_shards = data.draw(st.sampled_from(SHARD_COUNTS),
                             label="n_shards")
        m0 = data.draw(st.sampled_from(BOUNDARY_M), label="M0")
        positive = data.draw(st.booleans(), label="positive")
        compact_async = data.draw(st.booleans(), label="compact_async")
        seed = data.draw(st.integers(0, 2**32 - 1), label="seed")
        ops = data.draw(_OPS, label="ops")
        note(f"repro: seed={seed} n_shards={n_shards} M0={m0} "
             f"positive={positive} compact_async={compact_async} ops={ops}")
        rng = np.random.default_rng(seed)
        base = _rows(rng, m0, positive)
        cat = _make(base, n_shards, compact_async)
        shadow = {i: base[i] for i in range(m0)}
        try:
            _replay(cat, shadow, ops, rng, positive)
        finally:
            faults.disarm_all()
            cat.flush()

    @given(data=st.data())
    def test_ladder_and_flat_catalogue_agree(data):
        """Differential arm: the SAME fault-free schedule replayed on
        the LSM ladder and on the single-level catalogue ends in the
        SAME visible contents — identical {gid: row} maps — and both
        answer the same final queries exactly."""
        n_shards = data.draw(st.sampled_from([1, 4, 8]), label="n_shards")
        m0 = data.draw(st.sampled_from(BOUNDARY_M), label="M0")
        positive = data.draw(st.booleans(), label="positive")
        seed = data.draw(st.integers(0, 2**32 - 1), label="seed")
        ops = data.draw(_CLEAN_OPS, label="ops")
        note(f"repro: seed={seed} n_shards={n_shards} M0={m0} "
             f"positive={positive} ops={ops}")
        rng = np.random.default_rng(seed)
        base = _rows(rng, m0, positive)
        lsm = _make(base, n_shards, compact_async=False)
        flat = _make(base, 0, compact_async=False)
        shadow_l = {i: base[i] for i in range(m0)}
        shadow_f = {i: base[i] for i in range(m0)}
        # identical rng streams: replay consumes draws in the same order
        _replay(lsm, shadow_l, ops, np.random.default_rng(seed + 1),
                positive, faultable=False)
        _replay(flat, shadow_f, ops, np.random.default_rng(seed + 1),
                positive, faultable=False)
        assert shadow_l.keys() == shadow_f.keys()
        dl = {int(g): r for g, r in zip(*lsm.as_dense()[::-1])}
        df = {int(g): r for g, r in zip(*flat.as_dense()[::-1])}
        assert set(dl) == set(df) == set(shadow_l)
        for g in shadow_l:
            np.testing.assert_array_equal(dl[g], df[g])
            np.testing.assert_array_equal(dl[g], shadow_l[g])
else:                                                # pragma: no cover
    def test_interleaved_schedules_match_fresh_rebuild_oracle():
        pytest.importorskip("hypothesis")

    def test_ladder_and_flat_catalogue_agree():
        pytest.importorskip("hypothesis")


# -- deterministic companions ------------------------------------------------


def test_steady_state_folds_are_compile_free():
    """The §10 contract extended to the ladder: after warm(), a stream
    whose overflows are absorbed by L0 -> L1 folds triggers ZERO engine
    compiles and no new segmented-tail traces."""
    rng = np.random.default_rng(3)
    base = rng.standard_normal((64, R)).astype(np.float32)
    cat = ShardedLsmCatalogue(base, n_shards=4, delta_capacity=4,
                              l1_capacity=64, block_size=8,
                              compact_async=False)
    eng = get_engine("norm")
    cat.warm(K)
    U = rng.standard_normal((2, R)).astype(np.float32)
    # priming rounds: 5-row inserts cycle the delta occupancy through
    # every residue mod the capacity, so after one full cycle every
    # lazily-traced tail shape the steady state can present is cached
    for _ in range(4):
        cat.add_targets(rng.standard_normal((5, R)).astype(np.float32))
        cat.query(eng, U, K)
    folds0 = cat.stats.n_l1_folds
    tails0 = cat.trace_counts.get("segmented_tail", 0)
    shadow = {int(g): r for g, r in zip(*cat.as_dense()[::-1])}
    for _ in range(6):
        rows = rng.standard_normal((5, R)).astype(np.float32)
        for g, row in zip(cat.add_targets(rows), rows):
            shadow[int(g)] = row
        _check_query(cat, shadow, U)
    assert cat.stats.n_l1_folds > folds0          # the stream DID fold
    assert cat.stats.n_compactions == 0           # ...never a full rebuild
    assert cat.stats.engine_compiles_total == 0   # the §10 gate
    assert cat.trace_counts.get("segmented_tail", 0) == tails0


def test_norm_sharded_engine_on_ladder_is_exact():
    """The title configuration: the norm_sharded engine querying the
    sharded LSM catalogue (runs on 1 device under jax.shard_map; CI
    re-runs this file under 8 forced host devices)."""
    rng = np.random.default_rng(17)
    base = rng.standard_normal((96, R)).astype(np.float32)
    cat = ShardedLsmCatalogue(base, n_shards=4, delta_capacity=4,
                              l1_capacity=32, block_size=8,
                              compact_async=False)
    shadow = {i: base[i] for i in range(96)}
    rows = rng.standard_normal((9, R)).astype(np.float32)
    for g, row in zip(cat.add_targets(rows), rows):
        shadow[int(g)] = row
    cat.delete_targets([0, 50])
    del shadow[0], shadow[50]
    U = rng.standard_normal((3, R)).astype(np.float32)
    _check_query(cat, shadow, U, engine="norm_sharded")
    cat.promote(wait=True)
    assert cat.l1_rows == 0 and cat.l0_chain_len == 0
    _check_query(cat, shadow, U, engine="norm_sharded")
