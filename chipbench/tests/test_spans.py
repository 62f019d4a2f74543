"""Self-tests of the host-stage reduction (``spans.py``) on the CPU."""

import pathlib

import pytest

from chipbench import spans

DATA = pathlib.Path(__file__).resolve().parent / "data"
MS = 1_000_000                                  # ns


def _reduced():
    """A 100 ms window; the device busy over [10, 40] and [60, 90] ms,
    so idle over [0, 10], [40, 60] and [90, 100]."""
    device = [(10 * MS, 25 * MS), (20 * MS, 40 * MS), (60 * MS, 90 * MS),
              (-5 * MS, -1 * MS)]               # before the window
    host = [
        ("topk.await#batch=1,n=4#", 35 * MS, 62 * MS),   # 20 ms idle
        ("topk.await", 95 * MS, 120 * MS),      # clipped to 5 ms, all idle
        ("topk.enqueue#batch=1#", -3 * MS, 2 * MS),      # clipped to 2
        ("py.gc#generation=2#", 41 * MS, 43 * MS),       # under the await
        ("topk.account", 70 * MS, 80 * MS),     # device busy throughout
        ("np.asarray", 0, 100 * MS),            # not a stage
        ("topk-decomposer", 0, 100 * MS),       # an XLA pass, not a stage
        ("topk.route", 150 * MS, 160 * MS),     # after the window
    ]
    return spans.reduce((0, 100 * MS), host, device)


def test_stages_are_clipped_to_the_window_and_split_by_idle():
    st = _reduced()
    assert st.window_s == pytest.approx(0.1)
    assert st.idle_s == pytest.approx(0.040)
    aw = st.get("topk.await")
    assert aw.count == 2
    assert aw.seconds == pytest.approx(0.027 + 0.005)
    assert aw.idle_s == pytest.approx(0.020 + 0.005)
    enq = st.get("topk.enqueue")
    assert (enq.count, enq.seconds, enq.idle_s) == (1, pytest.approx(0.002),
                                                    pytest.approx(0.002))
    assert st.get("topk.account").idle_s == 0.0
    assert st.get("py.gc").seconds == pytest.approx(0.002)
    # the name is the part before '#'; XLA passes and JAX internals and
    # events outside the window are not stages
    assert set(st.by_name) == {"topk.await", "topk.enqueue",
                               "topk.account", "py.gc"}
    # a name with no events reads as zero
    assert st.get("topk.validate") == spans.Stage()
    # idle under some event: [0, 2], [40, 60], [95, 100] ms
    assert st.covered_idle_s == pytest.approx(0.027)


def test_the_longest_idle_gaps_name_the_events_under_them():
    st = _reduced()
    assert [round(g, 6) for g, _ in st.gaps] == [0.02, 0.01, 0.01]
    longest = dict(st.gaps[0][1])
    assert longest == {"topk.await": pytest.approx(0.020),
                       "py.gc": pytest.approx(0.002)}


def test_readings():
    st = spans.reduce(
        (0, 100 * MS),
        [("topk.coalesce", 0, 1 * MS), ("topk.route", 1 * MS, 2 * MS),
         ("topk.enqueue", 2 * MS, 4 * MS), ("topk.enqueue", 50 * MS,
                                            52 * MS),
         ("topk.validate", 48 * MS, 50 * MS),
         ("topk.await", 4 * MS, 10 * MS), ("topk.account", 10 * MS,
                                           13 * MS),
         ("topk.fulfil", 13 * MS, 14 * MS), ("py.gc", 60 * MS, 65 * MS)],
        [(5 * MS, 9 * MS), (60 * MS, 100 * MS)])
    assert spans.dispatch_host_ms_per_batch(st) == pytest.approx(3.0)
    assert spans.harvest_host_ms_per_batch(st) == pytest.approx(4.0)
    # await over [4, 10] ms, device busy over [5, 9]: 2 ms idle
    assert spans.readback_idle_ms_per_step(st, 2) == pytest.approx(1.0)
    # validate [48, 50] + enqueue [2, 4] and [50, 52], all idle
    assert spans.enqueue_idle_ms_per_step(st, 2) == pytest.approx(3.0)
    assert spans.gc_pause_ms_per_s(st) == pytest.approx(50.0)


def test_readings_are_none_without_stage_events():
    none = [spans.dispatch_host_ms_per_batch(None),
            spans.harvest_host_ms_per_batch(None),
            spans.readback_idle_ms_per_step(None, 10),
            spans.enqueue_idle_ms_per_step(None, 10),
            spans.gc_pause_ms_per_s(None)]
    assert none == [None] * 5
    # a program that marks no stage and no collection
    st = spans.reduce((0, 10 * MS), [("np.asarray", 0, MS)], [(0, MS)])
    assert st.by_name == {}
    assert [spans.dispatch_host_ms_per_batch(st),
            spans.harvest_host_ms_per_batch(st),
            spans.readback_idle_ms_per_step(st, 10),
            spans.enqueue_idle_ms_per_step(st, 10),
            spans.gc_pause_ms_per_s(st)] == [None] * 5


def test_recorded_chip_trace_of_a_program_without_stages():
    """The recorded v5e trace predates the stage events: the reduction
    finds the window and the device, and no stage."""
    st = spans.summarize(DATA / "trace")
    assert st is not None and st.by_name == {}
    assert 0 < st.idle_s < st.window_s
    assert st.covered_idle_s == 0.0
    assert spans.gc_pause_ms_per_s(st) is None


def test_no_trace_no_summary(tmp_path):
    assert spans.summarize(tmp_path) is None


def test_the_tool_refuses_to_run_without_a_tpu(capsys):
    from chipbench import harness, host_stages, tracing
    measure = harness.measure
    assert host_stages.main(["--workload", "lmhead_ds67b.decode128",
                             "--seed", "1", "--seconds", "1"]) == 2
    assert "no TPU" in capsys.readouterr().err
    # and leaves the harness as it found it
    assert harness.measure is measure
    assert tracing.summarize.__module__ == "chipbench.tracing"
