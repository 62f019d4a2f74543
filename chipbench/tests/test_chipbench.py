"""Self-tests of the chip benchmark on the CPU (see conftest.py)."""

import hashlib
import json
import pathlib
import time

import numpy as np
import pytest

from chipbench import (check, control, harness, readers, reference, spec,
                       tracing, traffic, work)

DATA = pathlib.Path(__file__).resolve().parent / "data"
CELLS = ["retrieval_1m.poisson", "lmhead_ds67b.decode128"]


# -- trace reduction ---------------------------------------------------------

def test_union_length():
    total, merged = tracing.union_length([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert total == 6
    assert merged == [[0, 3], [5, 8]]


def test_trace_reduction_on_recorded_chip_trace():
    """A trace recorded on one TPU v5e: AsyncTopKServer, naive, M=65,536,
    R=64, k=100, 24 requests in 4 batches. The reduction must agree with
    a plain recount of the same events."""
    from jax.profiler import ProfileData

    s = tracing.summarize(DATA / "trace")
    assert s is not None
    pd = ProfileData.from_file(str(tracing.find_xplane(DATA / "trace")))
    host = [e for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events]
    win = [e for e in host if e.name == "bench.window"][0]
    dev = pd.find_plane_with_name("/device:TPU:0")
    lines = {line.name: list(line.events) for line in dev.lines}
    ops = [e for e in lines["XLA Ops"]
           if win.start_ns <= e.start_ns and e.end_ns <= win.end_ns]
    runs = [e for e in lines["XLA Modules"] if e.name.startswith("jit_run(")
            and win.start_ns <= e.start_ns and e.end_ns <= win.end_ns]
    # busy: paint a ns grid (the plain way) and count it
    grid = np.zeros(int(win.end_ns - win.start_ns) + 1, bool)
    for e in ops:
        grid[int(e.start_ns - win.start_ns):int(e.end_ns - win.start_ns)] \
            = True
    assert s.window_s == pytest.approx(win.duration_ns / 1e9)
    assert s.busy_s == pytest.approx(grid.sum() / 1e9, rel=1e-3)
    assert s.executor_calls == len(runs) == 4
    assert s.executor_s == pytest.approx(
        sum(e.duration_ns for e in runs) / 1e9)
    topk = sum(e.duration_ns for e in ops if 'custom_call_target="TopK"'
               in e.name) / 1e9
    assert s.topk_s == pytest.approx(topk) and 0 < topk < s.executor_s
    assert s.device_ops[0][0] == "custom-call:TopK"
    gaps = [g for _, g in s.idle_gaps]
    assert gaps == sorted(gaps, reverse=True) and len(gaps) <= 10
    assert sum(gaps) <= s.window_s - s.busy_s + 1e-9
    assert gaps[0] > 0 and all(label for label, _ in s.idle_gaps)


def test_trace_summary_absent_without_a_trace(tmp_path):
    assert tracing.summarize(tmp_path) is None


# -- the naive work function --------------------------------------------------

def test_naive_work_function():
    peak = work.peaks("TPU v5 lite")
    assert work.naive_flops(4, 1000, 64) == 2 * 4 * 1000 * 64
    assert work.naive_bytes(4, 1000, 64, 10) == 4 * (1000 * 64 + 4 * 64) \
        + 8 * 4 * 10
    # R = 64, B = 64: 32 flop/byte, under the v5e ridge: memory bound
    b, m, r, k = 64, 1_000_000, 64, 100
    assert work.least_time_s(b, m, r, k, peak) == pytest.approx(
        work.naive_bytes(b, m, r, k) / 819e9)
    # B = 4096 at R = 8192 is past the ridge: compute bound
    assert work.least_time_s(4096, 102400, 8192, 50, peak) == \
        pytest.approx(work.naive_flops(4096, 102400, 8192) / 197e12)
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_roofline_reader_counts_live_rows():
    tr = tracing.Summary(1.0, 0.5, executor_s=0.01, executor_calls=2,
                         topk_s=0.004, device_ops=[], idle_gaps=[])
    run = harness.Run(cell="c", config={"rows": 1_000_000, "rank": 64,
                                        "k": 100},
                      traffic={}, setup_s=1.0, window_s=1.0,
                      latency_s=np.zeros(3), n_exact=3,
                      batches=[(1, 1), (64, 1)], trace=tr,
                      peak=work.peaks("TPU v5 lite"))
    least = (work.naive_bytes(1, 1_000_000, 64, 100)
             + work.naive_bytes(64, 1_000_000, 64, 100)) / 819e9
    assert readers.scan_roofline(run) == pytest.approx(100 * least / 0.01)
    assert readers.topk_share(run) == pytest.approx(40.0)
    assert readers.idle_share(run) == pytest.approx(50.0)
    run.trace = None
    assert readers.scan_roofline(run) is None


# -- latency from the due time ------------------------------------------------

class _Handle:
    def __init__(self, k):
        self.k, self.t = k, time.perf_counter()

    def result(self, timeout=None):
        time.sleep(max(0.0, self.t + 0.001 - time.perf_counter()))
        from repro.core.naive import TopKResult
        return TopKResult(np.zeros((1, self.k), np.float32),
                          np.zeros((1, self.k), np.int32),
                          np.zeros(1), np.zeros(1))


class _StallingServer:
    """Answers 1 ms after submit; its 10th submit stalls the caller."""

    def __init__(self, stall_s):
        self.stall_s, self.n = stall_s, 0

    def submit(self, u, k, method=None):
        self.n += 1
        if self.n == 10:
            time.sleep(self.stall_s)
        return _Handle(k)


def test_latency_counts_a_generator_stall_from_the_due_time():
    offsets = np.arange(100) * 0.002           # one request every 2 ms
    rows = np.zeros((100, 4), np.float32)
    t0, sent, done, _, _ = harness.drive_open(_StallingServer(0.05), rows,
                                              offsets, 3, "naive")
    lat = done - (t0 + offsets)
    lag = sent - (t0 + offsets)
    # the 10th submit stalls 50 ms: its own answer, and the requests
    # due during the stall, are late by what they waited
    assert lat[9] > 0.045
    assert lag[10] > 0.04 and lat[10] > 0.04 and lat[12] > 0.035
    # timing from the send would have hidden the stall
    assert np.all(lat[10:13] >= lag[10:13])
    # and the generator caught up
    assert np.median(lag[-20:]) < 0.005


# -- finding configs, traffic and metrics by name -----------------------------

def _digest(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()}


def test_a_new_cell_and_metric_are_new_files_only(tmp_path, tiny):
    base, bench = tiny
    before = _digest(base)
    cfg = json.loads((base / "configs" / "retrieval_1m.json").read_text())
    cfg.update(name="retrieval_2k", rows=2048)
    (base / "configs" / "retrieval_2k.json").write_text(json.dumps(cfg))
    (base / "traffic" / "bursty.json").write_text(json.dumps(
        {"loop": "open", "entry": "submit", "rate_per_s": 200,
         "burst": {"period_ms": 200, "on_ms": 50}}))
    (base / "metrics" / "answered_share.py").write_text(
        "import numpy as np\n\n\ndef read(run):\n"
        "    return 100.0 * float(np.isfinite(run.latency_s).mean())\n")
    after = _digest(base)
    assert {n: after[n] for n in before} == before   # nothing edited
    bench = json.loads(json.dumps(bench))
    bench["configs"].append({"name": "retrieval_2k"})
    bench["workloads"].append({"name": "retrieval_2k.bursty",
                               "config": "retrieval_2k",
                               "traffic": "bursty", "chips": 1})
    bench["per_layer"].append({"name": "answered_share", "unit": "%",
                               "moves": "p99_ms",
                               "workloads": ["retrieval_2k.bursty"]})
    cell = spec.workload(bench, "retrieval_2k.bursty")
    assert spec.load_config(cell["config"], base)["rows"] == 2048
    assert spec.load_traffic(cell["traffic"], base)["burst"]["on_ms"] == 50
    names = [m["name"] for m in spec.cell_metrics(bench,
                                                  "retrieval_2k.bursty", True)]
    assert "answered_share" in names and "compiles_in_window" not in names
    assert {m["name"] for m in spec.cell_metrics(
        bench, "retrieval_2k.bursty", False)} == {"setup_s", "p50_ms",
                                                  "p99_ms"}
    run, verdict, _ = harness.measure("retrieval_2k.bursty", 3, 0.6, False,
                                      time.perf_counter(), base=base,
                                      bench=bench)
    assert verdict.correct
    got = spec.read_metrics(spec.cell_metrics(bench, "retrieval_2k.bursty",
                                              True), run, base)
    assert got["answered_share"] == {"value": 100.0, "unit": "%"}


def test_every_metric_of_the_benchmark_has_a_reader():
    bench = spec.load_benchmark()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))
    for w in bench["workloads"]:
        spec.load_config(w["config"])
        spec.load_traffic(w["traffic"])


# -- the traffic generator ----------------------------------------------------

def test_every_seed_gets_the_same_amount_of_work():
    t = {"loop": "open", "rate_per_s": 500}
    a = traffic.arrival_offsets(t, 2**40 + 3, 2.0)
    b = traffic.arrival_offsets(t, 7, 2.0)
    assert len(a) == len(b) == 1000
    assert np.all(np.diff(a) >= 0) and 0 <= a[0] and a[-1] < 2.0
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a, traffic.arrival_offsets(t, 2**40 + 3,
                                                             2.0))
    burst = dict(t, burst={"period_ms": 200, "on_ms": 50})
    c = traffic.arrival_offsets(burst, 9, 2.0)
    assert len(c) == 1000 and np.all((c % 0.2) < 0.05 + 1e-12)


def test_reference_blocks_remake_the_catalogue():
    from chipbench import catalogue
    cfg = {"rows": 1000, "rank": 16, "block_rows": 256,
           "catalogue": {"kind": "lowrank_spectrum"}}
    whole = np.asarray(catalogue.rows(2**35 + 1, catalogue.CATALOGUE, 1000,
                                      16, cfg["catalogue"], 256), np.float64)
    parts = np.concatenate([reference.catalogue_block(2**35 + 1, cfg, b)[1]
                            for b in range(4)])
    np.testing.assert_array_equal(whole, parts)


# -- each cell's path at a tiny size ------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("traced", [False, True])
def test_rehearsal(cell, traced, tiny):
    base, bench = tiny
    run, verdict, _ = harness.measure(cell, 2**33 + 5, 0.6, traced,
                                      time.perf_counter(), base=base,
                                      bench=bench)
    assert verdict.correct, verdict.as_dict()
    assert verdict.numbers["unanswered"] == 0
    assert run.compiles == 0
    got = spec.read_metrics(spec.cell_metrics(bench, cell, traced), run,
                            base)
    want = {m["name"] for m in spec.cell_metrics(bench, cell, traced)}
    if traced:
        # off the chip the trace has no TPU plane: its readers stay silent
        assert run.trace is None
        assert "compiles_in_window" in got
        assert not {n for n in got if "roofline" in n or "share" in n}
    else:
        assert set(got) == want
        assert got["p99_ms"]["value"] >= got["p50_ms"]["value"] > 0


def test_the_command_refuses_to_run_without_a_tpu(capsys):
    from chipbench import run
    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                     "1"]) == 2
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


# -- the check: faults planted in the timed path, and the control -------------

def _planted(fault):
    """Swap the naive executor for one that breaks its answers."""
    import jax
    import jax.numpy as jnp

    from repro.core import engines

    sound = engines._ARG_EXECUTORS["naive"]

    def broken(args, U, k, cfg):
        res = sound(args, U, k=k, cfg=cfg)
        if fault == "altered":
            # one answer's best id swapped for another row's
            ids = res.indices.at[0, 0].set((res.indices[0, 0] + 1)
                                           % args["m_real"])
            return res._replace(indices=ids)
        if fault == "rows95":
            # the top-k over the first 95 % of the rows only
            return sound(dict(args, m_real=args["m_real"] * 19 // 20), U,
                         k=k, cfg=cfg)
        # half the batch left out: its rows get the first half's answers
        b = U.shape[0]
        h = max(b // 2, 1)
        idx = jnp.arange(b) % h
        return jax.tree_util.tree_map(
            lambda a: a[idx] if a.ndim and a.shape[0] == b else a, res)

    return sound, jax.jit(broken, static_argnames=("k", "cfg"))


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["altered", "half_batch", "rows95"])
def test_a_broken_timed_path_is_not_correct(cell, fault, tiny, monkeypatch):
    from repro.core import engines

    base, bench = tiny
    sound, broken = _planted(fault)
    monkeypatch.setitem(engines._ARG_EXECUTORS, "naive", broken)
    _, verdict, _ = harness.measure(cell, 11, 0.6, False,
                                    time.perf_counter(), base=base,
                                    bench=bench)
    assert not verdict.correct, verdict.as_dict()


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_is_not_correct(cell, tiny):
    base, bench = tiny
    r = control.reading(cell, 4, 0.6, "control", base=base, bench=bench)
    assert not r["correct"], r
    assert r["checks"]["score_err"]["value"] > \
        r["checks"]["score_err"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_a_top_k_that_skips_rows_is_misranked(cell, tiny):
    base, bench = tiny
    r = control.reading(cell, 4, 0.6, "rows95", base=base, bench=bench)
    assert not r["correct"], r
    assert r["checks"]["misranked"]["value"] > 0
    assert r["checks"]["score_err"]["value"] <= \
        r["checks"]["score_err"]["limit"]


def test_misranked_tolerance_follows_the_score_err_limit():
    """A served item that swaps with its neighbour counts as misranked
    when the gap is over twice the ``score_err`` limit, not under."""
    from chipbench.reference import Reference
    unit = check.EPS32 * 100.0
    ref_vals = np.array([[10.0, 10.0 - 50 * unit, 10.0 - 900 * unit]])
    served_ids = np.array([[0, 2, 1]])          # the last two swapped
    served_true = ref_vals[:, [0, 2, 1]]
    ref = Reference(ref_vals, np.array([[0, 1, 2]]), served_true,
                    np.full((1, 3), 100.0))
    for limit, want in ((20.0, 1.0), (500.0, 0.0)):
        v = check.judge(served_true, served_ids, ref, 3, 0,
                        {"score_err": limit})
        assert v.numbers["misranked"] == want
        assert v.numbers["score_err"] == 0.0


def test_bad_ids_are_counted():
    ids = np.array([[3, 4, 3], [0, 1, 9]])
    bad = check.bad_id_mask(ids, 5)
    assert bad.sum(axis=1).tolist() == [1, 1]
