"""The profiler trace of a window, reduced to numbers.

A traced run records the window with ``jax.profiler`` (Python function
tracing off: at thousands of requests a second it would be most of the
trace). The reduction reads the ``.xplane.pb`` with
``jax.profiler.ProfileData``:

- the window is the host event the harness names ``bench.window``;
- device time is the ``XLA Ops`` line of each ``/device:TPU:<n>``
  plane, clipped to the window; busy time is the union of those
  intervals, averaged over the chips;
- the naive executor's runs are the ``XLA Modules`` events named
  ``jit_run(...)`` (the engines' shared executor function); its top-k
  is the op with ``custom_call_target="TopK"``;
- an idle gap is a stretch of the window with no op on the first
  device; it is labelled with the innermost host event under its
  midpoint on each host thread that has one.
"""

from __future__ import annotations

import bisect
import dataclasses
import pathlib
import re
from typing import List, Optional, Tuple

EXECUTOR_PREFIX = "jit_run("
TOPK_MARK = 'custom_call_target="TopK"'
_OP_NAME = re.compile(r"^%([A-Za-z_\-]+(?:\.[A-Za-z_\-]+)*)")


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


@dataclasses.dataclass
class Summary:
    window_s: float
    #: device busy time in the window, averaged over the chips
    busy_s: float
    #: summed device time of the executor's runs, and their number
    executor_s: float
    executor_calls: int
    #: device time of the top-k op inside the executor's runs
    topk_s: float
    #: ``(op, seconds)``, the ops that took most device time
    device_ops: List[Tuple[str, float]]
    #: ``(what the host was doing, seconds)``, the longest idle gaps
    idle_gaps: List[Tuple[str, float]]

    def breakdown(self) -> dict:
        return {"device_ops": [list(x) for x in self.device_ops],
                "idle_gaps": [list(x) for x in self.idle_gaps]}


def op_label(name: str) -> str:
    """``%convolution_select_fusion.3 = ...`` -> ``convolution_select_
    fusion``; a custom call is named by its target."""
    m = _OP_NAME.match(name)
    label = m.group(1) if m else name.split(" ", 1)[0]
    target = re.search(r'custom_call_target="([^"]+)"', name)
    if target:
        label = f"{label}:{target.group(1)}"
    return label[:200]


def union_length(intervals) -> Tuple[float, list]:
    """Total length of a set of ``(start, end)`` intervals, and the
    merged intervals, sorted."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def find_xplane(log_dir) -> Optional[pathlib.Path]:
    files = sorted(pathlib.Path(log_dir).glob("**/*.xplane.pb"))
    return files[-1] if files else None


def summarize(log_dir, window_name: str = "bench.window",
              n_ops: int = 10, n_gaps: int = 10) -> Optional[Summary]:
    """Reduce the trace under ``log_dir``; None where it holds no
    window or no TPU plane."""
    from jax.profiler import ProfileData

    path = find_xplane(log_dir)
    if path is None:
        return None
    pd = ProfileData.from_file(str(path))
    host_lines, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            host_lines += [list(line.events) for line in plane.lines]
        elif plane.name.startswith("/device:TPU:"):
            devices.append({line.name: list(line.events)
                            for line in plane.lines})
    window = [e for events in host_lines for e in events
              if e.name == window_name]
    if not window or not devices:
        return None
    w0, w1 = window[0].start_ns, window[0].end_ns

    def clip(e):
        return max(e.start_ns, w0), min(e.end_ns, w1)

    busy, exe, calls, topk = 0.0, 0.0, 0, 0.0
    op_time: dict = {}
    merged0 = None
    for lines in devices:
        ops = [e for e in lines.get("XLA Ops", ())
               if e.end_ns > w0 and e.start_ns < w1]
        b, merged = union_length([clip(e) for e in ops])
        busy += b
        merged0 = merged if merged0 is None else merged0
        runs = [clip(e) for e in lines.get("XLA Modules", ())
                if e.name.startswith(EXECUTOR_PREFIX)
                and e.end_ns > w0 and e.start_ns < w1]
        exe += sum(e - s for s, e in runs)
        calls += len(runs)
        runs_sorted = sorted(runs)
        for e in ops:
            s, t = clip(e)
            label = op_label(e.name)
            op_time[label] = op_time.get(label, 0.0) + (t - s)
            if TOPK_MARK in e.name and _inside(s, runs_sorted):
                topk += t - s
    n = len(devices)
    ops_top = sorted(op_time.items(), key=lambda x: -x[1])[:n_ops]
    gaps = []
    prev = w0
    for s, e in merged0 + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n_gaps]
    return Summary(
        window_s=(w1 - w0) / 1e9, busy_s=busy / n / 1e9,
        executor_s=exe / n / 1e9, executor_calls=calls // n,
        topk_s=topk / n / 1e9,
        device_ops=[(name, t / n / 1e9) for name, t in ops_top],
        idle_gaps=[(_host_label(host_lines, (s + e) / 2, window_name),
                    (e - s) / 1e9) for s, e in gaps])


def _inside(t: float, runs) -> bool:
    """Whether ``t`` falls in one of the sorted ``(start, end)`` runs."""
    i = bisect.bisect_right(runs, (t, float("inf"))) - 1
    return i >= 0 and runs[i][0] <= t <= runs[i][1]


def _host_label(host_lines, t: float, window_name: str) -> str:
    """Innermost host event under ``t`` on each thread that has one."""
    names = []
    for events in host_lines:
        under = [e for e in events if e.start_ns <= t <= e.end_ns
                 and e.name != window_name]
        if under:
            inner = min(under, key=lambda e: e.duration_ns)
            if inner.name not in names:
                names.append(inner.name)
    return (" | ".join(names) or "no host event")[:200]
