"""Observability layer (DESIGN.md §14): registry/trace/journal unit
tests, the ServeStats façade contract, thread-safety under concurrent
recording + compaction, and the end-to-end span↔journal join."""

import gc
import threading

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import SepLRModel, certificate_gaps, faults
from repro.core.engines import batch_bucket
from repro.serving.pipeline import AsyncTopKServer
from repro.serving.server import LATENCY_RING, ServeStats, TopKServer


#: the process-wide tracer's rate, before any test here changes it
DEFAULT_SAMPLE_RATE = obs.TRACER.sample_rate


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test sees empty default stores, an enabled layer and a
    tracer that keeps every request."""
    obs.reset()
    obs.set_enabled(True)
    obs.TRACER.sample_rate = 1.0
    yield
    obs.reset()
    obs.set_enabled(True)
    obs.TRACER.sample_rate = DEFAULT_SAMPLE_RATE


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_labels_and_total():
    reg = obs.MetricsRegistry()
    c = reg.counter("t_requests_total", "x", labels=("engine",))
    c.inc(engine="bta")
    c.inc(2, engine="norm")
    assert c.value(engine="bta") == 1
    assert c.value(engine="norm") == 2
    assert c.value(engine="nope") == 0
    assert c.total() == 3


def test_registry_get_or_create_rejects_mismatch():
    reg = obs.MetricsRegistry()
    reg.counter("t_thing", "x", labels=("a",))
    assert reg.counter("t_thing", "x", labels=("a",)) is reg.get("t_thing")
    with pytest.raises(ValueError):
        reg.counter("t_thing", "x", labels=("b",))
    with pytest.raises(ValueError):
        reg.gauge("t_thing", "x", labels=("a",))
    with pytest.raises(ValueError):
        reg.counter("bad name!", "x")


def test_histogram_ring_percentile_matches_numpy():
    reg = obs.MetricsRegistry()
    h = reg.histogram("t_lat_us", "x", buckets=obs.LATENCY_BUCKETS_US,
                      ring=64)
    rng = np.random.default_rng(3)
    vals = rng.lognormal(5, 2, size=200)
    for v in vals:
        h.observe(float(v))
    window = np.asarray(list(h.ring()))
    assert len(window) == 64
    for q in (50, 95, 99):
        assert h.percentile(q) == pytest.approx(
            float(np.percentile(window, q)))
    assert h.count() == 200
    assert h.mean() == pytest.approx(float(vals.mean()))


def test_histogram_bucketless_series_and_empty():
    reg = obs.MetricsRegistry()
    h = reg.histogram("t_noring", "x", buckets=(1.0, 10.0, 100.0))
    assert h.percentile(50) == 0.0
    h.observe(5.0)
    assert h.percentile(50) == 10.0  # bucket upper-bound estimate
    with pytest.raises(ValueError):
        h.ring()   # no ring kept


def test_snapshot_validates_and_prom_parses():
    obs.on_batch_served("bta", 4, 100, 40, 1000, 250.0, "nonneg")
    obs.on_degradation("bta", "shed")
    obs.on_compaction("success", duration_s=0.01, version=1, epoch=2)
    snap = obs.REGISTRY.snapshot()
    obs.validate_snapshot(snap)          # raises on violation
    samples = obs.parse_prom_text(obs.REGISTRY.render_prom())
    assert samples['repro_queries_total{engine="bta"}'] == 4
    assert samples["repro_shed_total"] == 1
    assert samples["repro_compaction_seconds_count"] == 1
    # histogram cumulative buckets present
    assert any(k.startswith("repro_batch_latency_us_bucket")
               for k in samples)


def test_snapshot_schema_rejects_garbage():
    with pytest.raises(ValueError):
        obs.validate_snapshot({"nope": 1})
    with pytest.raises(ValueError):
        obs.validate_snapshot(
            {"metrics": {"m": {"kind": "sundial", "help": "",
                               "labels": [], "series": []}}})


def test_disable_switch_stops_recording():
    obs.set_enabled(False)
    obs.on_batch_served("bta", 4, 100, 40, 1000, 250.0)
    obs.on_fault_fired("compaction.build")
    assert obs.QUERIES.total() == 0
    assert len(obs.JOURNAL) == 0
    assert obs.TRACER.start_trace("x") is None
    obs.set_enabled(True)
    obs.on_batch_served("bta", 4, 100, 40, 1000, 250.0)
    assert obs.QUERIES.total() == 4


def test_topk_select_counts_each_dispatched_batch_by_path():
    """``repro_topk_select_total{engine, path}``: one per batch the
    server dispatches, on the path the shape rule names (8,192 rows:
    two-stage up to k = 32, direct past it); nothing while disabled."""
    rng = np.random.default_rng(2)
    T = rng.standard_normal((8192, 4)).astype(np.float32)
    srv = TopKServer(SepLRModel(T), max_batch=4)
    U = rng.standard_normal((6, 4)).astype(np.float32)
    srv.query(U, k=5, method="naive")          # chunks of 4 and 2
    srv.query(U[:3], k=33, method="naive")
    assert obs.TOPK_SELECT.value(engine="naive", path="two_stage") == 2
    assert obs.TOPK_SELECT.value(engine="naive", path="direct") == 1
    srv.query(U[:2], k=5, method="bta")        # no selection path of its own
    assert obs.TOPK_SELECT.total() == 3

    obs.set_enabled(False)
    srv.query(U, k=5, method="naive")
    srv.query(U[:3], k=33, method="naive")
    assert obs.TOPK_SELECT.total() == 3


# ---------------------------------------------------------------------------
# trace spans + event journal
# ---------------------------------------------------------------------------

def test_tracer_every_nth_sampling_is_deterministic():
    tr = obs.Tracer(capacity=16, sample_rate=0.25)
    kept = [tr.start_trace("t") is not None for _ in range(100)]
    assert sum(kept) == 25
    tr2 = obs.Tracer(capacity=16, sample_rate=0.25)
    assert kept == [tr2.start_trace("t") is not None for _ in range(100)]


def test_trace_tree_and_store_bound():
    tr = obs.Tracer(capacity=2)
    for i in range(3):
        t = tr.start_trace("req", k=i)
        t.span("queue_wait", start=0.0, end=0.5)
        t.span("await", start=0.5, end=1.0, engine="bta")
        t.finish()
    done = tr.traces()
    assert len(done) == 2          # bounded store evicted the oldest
    tree = done[-1].format_tree()
    assert "queue_wait" in tree and "engine=bta" in tree
    assert done[-1].find("await").duration_us == pytest.approx(5e5)


def test_journal_filter_tail_and_capacity():
    j = obs.EventJournal(capacity=8)
    for i in range(12):
        j.emit("tick", i=i, kind_field="x")
    assert len(j) == 8
    assert [e.fields["i"] for e in j.tail(3)] == [9, 10, 11]
    assert len(j.events("tick", i=10)) == 1
    assert j.counts() == {"tick": 12}   # lifetime, survives eviction
    # seq increases across eviction; as_dict round-trips
    d = j.tail(1)[0].as_dict()
    assert d["kind"] == "tick" and d["seq"] == 12


# ---------------------------------------------------------------------------
# ServeStats façade + mutation_stats schema
# ---------------------------------------------------------------------------

def test_servestats_facade_unchanged():
    s = ServeStats()
    for i in range(LATENCY_RING + 57):
        s.lat_us_ring.append(float(i))   # legacy direct-append path
    assert len(s.lat_us_ring) == LATENCY_RING
    want = np.asarray(s.lat_us_ring)
    assert s.p50_us == pytest.approx(float(np.percentile(want, 50)))
    assert s.p99_us == pytest.approx(float(np.percentile(want, 99)))
    s.record_request_latency(100.0)
    s.record_request_latency(300.0)
    assert s.req_p50_us == pytest.approx(200.0)
    assert len(s.req_lat_us_ring) == 2
    s.record_batch(4, 100, 40, 0.001, 8, "nonneg")
    assert (s.n_queries, s.n_scored, s.depth_sum, s.delta_scored) == \
        (4, 100, 40, 8)
    assert s.sign_batches == {"nonneg": 1}
    assert s.scores_per_query == 25.0
    s.bump_degradation("shed")
    s.note_uncertified(2)
    assert s.degradations == {"shed": 1} and s.n_uncertified == 2


def test_servestats_records_when_obs_disabled():
    # the façade histograms are STANDALONE instruments: the obs master
    # switch must not dark the server's own serving stats (they are the
    # pre-§14 baseline behaviour, and the overhead bench's off-mode
    # still reads them)
    obs.set_enabled(False)
    s = ServeStats()
    s.record_batch(1, 10, 5, 0.001)
    s.record_request_latency(42.0)
    assert s.n_queries == 1 and len(s.lat_us_ring) == 1
    assert s.req_p50_us == pytest.approx(42.0)


def test_mutation_stats_matches_declared_schema():
    rng = np.random.default_rng(0)
    T = rng.standard_normal((193, 7)).astype(np.float32)
    srv = TopKServer(SepLRModel(T), delta_capacity=8)
    ms = srv.mutation_stats
    assert set(ms) == set(obs.MUTATION_STATS_SCHEMA)
    for key, field in obs.MUTATION_STATS_SCHEMA.items():
        assert isinstance(ms[key], field.type), key
        assert field.doc   # every key documented
    # drift in either direction raises
    with pytest.raises(KeyError):
        obs.build_mutation_stats({**ms, "surprise": 1})
    short = dict(ms)
    short.popitem()
    with pytest.raises(KeyError):
        obs.build_mutation_stats(short)


# ---------------------------------------------------------------------------
# thread-safety hammer
# ---------------------------------------------------------------------------

def test_concurrent_recording_loses_nothing():
    """N threads hammer a ServeStats + registry counters while another
    thread mutates/compacts the catalogue (cache invalidations, epoch
    bumps) and a reader spins percentiles: exact totals, no
    exceptions."""
    rng = np.random.default_rng(1)
    T = rng.standard_normal((211, 7)).astype(np.float32)
    srv = TopKServer(SepLRModel(T), delta_capacity=8)
    s = ServeStats()
    c = obs.REGISTRY.counter("t_hammer_total", "x", labels=("t",))
    N_THREADS, N_ITER = 8, 400
    errors = []
    go = threading.Event()

    def writer(tid):
        go.wait()
        try:
            for i in range(N_ITER):
                s.record_batch(1, 10, 5, 1e-6, 0, "s%d" % (i % 3))
                s.record_request_latency(float(i))
                c.inc(t=str(tid))
        except BaseException as e:   # noqa: BLE001 — the assertion
            errors.append(e)

    def reader():
        go.wait()
        try:
            for _ in range(N_ITER):
                s.p99_us, s.req_p50_us, s.scores_per_query
                obs.REGISTRY.render_prom()
        except BaseException as e:   # noqa: BLE001 — the assertion
            errors.append(e)

    def mutator():
        go.wait()
        try:
            for i in range(24):
                srv.add_targets(rng.standard_normal((4, 7))
                                .astype(np.float32))
        except BaseException as e:   # noqa: BLE001 — the assertion
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(N_THREADS)]
    threads += [threading.Thread(target=reader),
                threading.Thread(target=mutator)]
    for t in threads:
        t.start()
    go.set()
    for t in threads:
        t.join()
    assert not errors
    assert s.n_queries == N_THREADS * N_ITER
    assert s.n_scored == 10 * N_THREADS * N_ITER
    assert sum(s.sign_batches.values()) == N_THREADS * N_ITER
    assert c.total() == N_THREADS * N_ITER
    for tid in range(N_THREADS):
        assert c.value(t=str(tid)) == N_ITER
    assert srv.mutation_stats["n_compactions"] >= 1
    assert obs.CACHE_INVALIDATIONS.total() == 0  # no cache attached
    assert len(obs.JOURNAL.events("compaction.success")) >= 1


# ---------------------------------------------------------------------------
# live certificate metrics pinned against certificate_gaps
# ---------------------------------------------------------------------------

def test_certificate_metrics_match_ground_truth():
    rng = np.random.default_rng(5)
    T = rng.standard_normal((223, 7)).astype(np.float32)
    srv = TopKServer(SepLRModel(T))
    U = rng.standard_normal((4, 7)).astype(np.float32)
    budget = 3
    res = srv.query(U, k=5, method="norm", budget=budget)
    gaps = np.asarray(certificate_gaps(res))
    valid = np.asarray(res.indices) >= 0
    unc = np.logical_and(gaps > 0, np.isfinite(gaps))
    want_frac = 1.0 - unc.sum() / max(valid.sum(), 1)
    bucket = str(batch_bucket(budget))
    assert obs.CERTIFIED_FRACTION.count(
        engine="norm", budget_bucket=bucket) == 1
    assert obs.CERTIFIED_FRACTION.sum(
        engine="norm", budget_bucket=bucket) == pytest.approx(want_frac)
    if unc.any():
        want_gap = float(gaps[unc].mean())
        assert obs.UNCERTIFIED_GAP.sum(
            engine="norm", budget_bucket=bucket) == \
            pytest.approx(want_gap, rel=1e-5)
        n_unc_q = int(np.sum(np.any(unc, axis=1)))
        assert obs.UNCERTIFIED.value(engine="norm") == n_unc_q
        assert srv.stats["norm"].n_uncertified == n_unc_q


# ---------------------------------------------------------------------------
# fault seams + end-to-end span/journal join
# ---------------------------------------------------------------------------

def test_fault_firing_emits_event():
    with faults.injected("compaction.build", error=None, times=1):
        assert faults.fire("compaction.build")
    assert obs.FAULTS_FIRED.value(point="compaction.build") == 1
    ev = obs.JOURNAL.events("fault.fired")
    assert ev and ev[-1].fields["point"] == "compaction.build"


def test_async_request_span_joins_compaction_event():
    """The acceptance trace: one async request's span tree names the
    engine, the cost-table entry, queue/coalesce/enqueue/await stage
    durations, and the (version, epoch) it ran against — and that
    version joins to the compaction.success journal event that
    produced the snapshot."""
    rng = np.random.default_rng(9)
    T = rng.standard_normal((227, 7)).astype(np.float32)
    with AsyncTopKServer(SepLRModel(T), max_batch=8, delta_capacity=8,
                         method="bta") as srv:
        srv.warmup(4)
        obs.reset()   # drop warmup noise; keep the layer on
        # force a synchronous compaction: >capacity appends
        srv.add_targets(rng.standard_normal((9, 7)).astype(np.float32))
        comp = obs.JOURNAL.events("compaction.success")
        assert comp, "mutation burst must have compacted"
        version = comp[-1].fields["version"]
        h = srv.submit(rng.standard_normal(7).astype(np.float32), 4)
        h.result(timeout=30)
        traces = obs.TRACER.traces()
        assert traces
        t = traces[-1]
        # the stage ladder, in order, every span closed
        names = [s.name for s in t.spans]
        for stage in ("queue_wait", "coalesce", "route", "dispatch",
                      "enqueue", "await", "harvest", "merge"):
            assert stage in names, stage
        assert all(s.t_end is not None for s in t.spans)
        # enqueue and await tile the executor call to the host result
        assert t.find("enqueue").t_end == t.find("await").t_start
        enq = t.find("enqueue")
        assert enq.attrs["engine"] == "bta"
        assert "bta" in t.find("route").attrs["cost_entry"]
        assert t.find("queue_wait").duration_us >= 0.0
        # the JOIN: the span ran against the snapshot the journal's
        # compaction.success event says it produced
        assert enq.attrs["version"] == version
        assert t.root.attrs["version"] == version
        joined = obs.JOURNAL.events("compaction.success",
                                    version=enq.attrs["version"])
        assert len(joined) == 1
        # the registry saw the same request on its always-on counters
        assert obs.QUERIES.value(engine="bta") >= 1
        assert obs.REQUEST_LATENCY.count(engine="bta") >= 1


def test_async_request_span_joins_fold_event():
    """Same join discipline across the LSM ladder: an L0 -> L1 fold
    journals compaction.fold_l1 with the SAME (version, epoch) join keys
    as compaction.success, and a traced request that ran against the
    folded catalogue joins to it. A fold moves rows without changing
    visible contents, so it must NOT bump the epoch — the request's
    enqueue span carries the very same (version, epoch) the fold event
    recorded."""
    from repro.core import ShardedLsmCatalogue

    rng = np.random.default_rng(23)
    T = rng.standard_normal((113, 7)).astype(np.float32)
    with AsyncTopKServer(SepLRModel(T), max_batch=8, delta_capacity=8,
                         method="bta", n_shards=4) as srv:
        assert isinstance(srv.server.catalogue, ShardedLsmCatalogue)
        srv.warmup(4)
        obs.reset()   # drop warmup noise; keep the layer on
        # stage rows below capacity, then compact: the ladder seals the
        # delta and folds it into L1 inline (no full rebuild, no build
        # thread) — and, because a fold changes no visible contents, no
        # epoch bump either
        srv.add_targets(rng.standard_normal((5, 7)).astype(np.float32))
        srv.server.catalogue.compact(wait=True)
        folds = obs.JOURNAL.events("compaction.fold_l1")
        assert folds, "overflow must have folded, not rebuilt"
        assert not obs.JOURNAL.events("compaction.success")
        ev = folds[-1].fields
        assert ev["rows_folded"] >= 1 and ev["l1_rows"] >= 1
        h = srv.submit(rng.standard_normal(7).astype(np.float32), 4)
        h.result(timeout=30)
        t = obs.TRACER.traces()[-1]
        enq = t.find("enqueue")
        # the JOIN, both keys: the request ran against exactly the
        # (version, epoch) the fold event was journalled under
        assert enq.attrs["version"] == ev["version"]
        assert enq.attrs["epoch"] == ev["epoch"]
        joined = obs.JOURNAL.events("compaction.fold_l1",
                                    version=enq.attrs["version"],
                                    epoch=enq.attrs["epoch"])
        assert joined and joined[-1].fields == ev


# ---------------------------------------------------------------------------
# host stages, GC pauses and compiles on the profiler's clock
# ---------------------------------------------------------------------------

ASYNC_STAGES = ["topk.coalesce", "topk.route", "topk.enqueue",
                "topk.backpressure", "topk.await", "topk.account",
                "topk.fulfil"]
SYNC_STAGES = ["topk.validate", "topk.route", "topk.enqueue", "topk.await",
               "topk.account", "topk.fulfil"]


def _profiled(tmp_path, fn):
    """Run ``fn`` under a ``jax.profiler`` trace; return its host events
    named ``topk.*`` or ``py.gc`` as ``(name, start_ns, args)``, in
    start order."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.glob("**/*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(path))
    events = [(e.name, e.start_ns, dict(e.stats))
              for p in pd.planes if p.name.startswith("/host:")
              for line in p.lines for e in line.events
              if e.name.startswith("topk.") or e.name == "py.gc"]
    return sorted(events, key=lambda e: e[1])


def _by_batch(events):
    out = {}
    for name, _, args in events:
        if name.startswith("topk."):
            out.setdefault(args["batch"], []).append((name, args["n"]))
    return out


def test_async_pipeline_stages_land_in_the_profile(tmp_path):
    """Every stage of the async path is one profiler event per
    micro-batch, in order, carrying the batch's sequence number and real
    size; a sampled request's root span carries the same number; a
    collection inside the trace is a ``py.gc`` event."""
    rng = np.random.default_rng(4)
    T = rng.standard_normal((227, 7)).astype(np.float32)
    Q = rng.standard_normal((5, 7)).astype(np.float32)
    with AsyncTopKServer(SepLRModel(T), max_batch=8,
                         method="naive") as srv:
        srv.warmup(4, engines=["naive"])

        def serve():
            for q in Q:                  # one at a time: a batch each
                srv.submit(q, 4).result(timeout=30)
            gc.collect()

        events = _profiled(tmp_path, serve)
    batches = _by_batch(events)
    assert len(batches) == len(Q)
    for seq, stages in batches.items():
        assert stages == [(name, 1) for name in ASYNC_STAGES], seq
    roots = {t.root.attrs["batch"] for t in obs.TRACER.traces()}
    assert roots == set(batches)
    gcs = [args for name, _, args in events if name == "py.gc"]
    assert {"generation": 2} in gcs


def test_sync_query_stages_land_in_the_profile(tmp_path):
    """``TopKServer.query``: validate and fulfil once per call, route to
    account once per chunk; the first chunk shares the call's number."""
    rng = np.random.default_rng(5)
    srv = TopKServer(SepLRModel(
        rng.standard_normal((300, 6)).astype(np.float32)), max_batch=8)
    srv.warmup(3, batch_sizes=(4, 8), engines=["naive"])
    U1 = rng.standard_normal((4, 6)).astype(np.float32)
    U2 = rng.standard_normal((12, 6)).astype(np.float32)

    def serve():
        srv.query(U1, 3, method="naive")
        srv.query(U2, 3, method="naive")

    batches = _by_batch(_profiled(tmp_path, serve))
    (a, b, c) = sorted(batches)
    assert batches[a] == [(name, 4) for name in SYNC_STAGES]
    assert batches[b] == [("topk.validate", 12), ("topk.route", 8),
                          ("topk.enqueue", 8), ("topk.await", 8),
                          ("topk.account", 8), ("topk.fulfil", 12)]
    assert batches[c] == [(name, 4) for name in SYNC_STAGES[1:-1]]


def test_a_disabled_layer_opens_no_stage_and_keeps_no_hook(tmp_path):
    rng = np.random.default_rng(6)
    srv = TopKServer(SepLRModel(
        rng.standard_normal((100, 5)).astype(np.float32)), max_batch=4)
    U = rng.standard_normal((2, 5)).astype(np.float32)
    srv.query(U, 2, method="naive")
    obs.set_enabled(False)
    assert obs._on_gc not in gc.callbacks
    compiles = obs.XLA_COMPILES.total()
    journalled = len(obs.JOURNAL.events("xla.compile"))

    def serve():
        srv.query(U, 2, method="naive")
        jax.jit(lambda x: x + 3)(np.ones(3))   # a fresh compile
        gc.collect()

    assert _profiled(tmp_path, serve) == []
    assert obs.XLA_COMPILES.total() == compiles
    assert len(obs.JOURNAL.events("xla.compile")) == journalled
    obs.set_enabled(True)
    assert gc.callbacks.count(obs._on_gc) == 1


def test_default_tracer_keeps_one_request_in_a_hundred():
    assert DEFAULT_SAMPLE_RATE == 0.01
    assert obs.Tracer().sample_rate == 1.0
    tr = obs.Tracer(capacity=64, sample_rate=DEFAULT_SAMPLE_RATE)
    kept = [tr.start_trace("t") is not None for _ in range(1000)]
    assert sum(kept) == 10 and kept.index(True) == 99


def test_compile_listener_names_the_function():
    def add_seven(x):
        return x + 7

    jax.jit(add_seven)(np.ones(5, np.float32))
    assert obs.XLA_COMPILES.value(fun="jit(add_seven)") == 1
    ev = obs.JOURNAL.events("xla.compile", fun="jit(add_seven)")
    assert len(ev) == 1 and ev[0].fields["seconds"] > 0


def test_gc_pause_is_recorded_without_waiting_on_a_held_lock():
    """A collection that interrupts a holder of the pause histogram's
    lock on the same thread must not wait on it: the pause is kept and
    recorded at the next collection."""
    before = obs.GC_PAUSE.count(generation="2")
    with obs.GC_PAUSE._lock:
        gc.collect()
    assert obs.GC_PAUSE.count(generation="2") == before
    gc.collect()
    assert obs.GC_PAUSE.count(generation="2") >= before + 2
    assert obs.GC_PAUSE.sum(generation="2") > 0
