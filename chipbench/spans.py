"""The program's host stages in a traced window, reduced to numbers.

The program marks each host stage of its served path with a profiler
event named ``topk.<stage>`` and each Python garbage collection with
``py.gc`` (``repro.obs``). This reads them from the window's
``.xplane.pb`` with ``jax.profiler.ProfileData``, as ``tracing.py``
reads the device:

- the window is the host event ``bench.window``;
- device idle is the window less the union of the first device's
  ``XLA Ops``;
- every host event named ``topk.*`` or ``py.gc`` (the name before any
  ``#``) is clipped to the window, and counted per name with its
  seconds and its seconds over device idle.

The readings at the end are what the per-layer metrics of the host
stages read; each is None where the trace holds no such event, as in a
program that writes none.
"""

from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, Iterable, List, Optional, Tuple

from chipbench.tracing import find_xplane, union_length

GC = "py.gc"
PREFIX = "topk."


@dataclasses.dataclass
class Stage:
    count: int = 0
    #: summed seconds inside the window
    seconds: float = 0.0
    #: the part of those seconds over device idle
    idle_s: float = 0.0


@dataclasses.dataclass
class Stages:
    window_s: float
    #: device idle in the window
    idle_s: float
    #: device idle under at least one stage or ``py.gc`` event
    covered_idle_s: float
    by_name: Dict[str, Stage]
    #: the longest idle gaps: ``(seconds, [(event, seconds under it)])``
    gaps: List[Tuple[float, List[Tuple[str, float]]]]

    def get(self, name: str) -> Stage:
        return self.by_name.get(name, Stage())

    def as_dict(self) -> dict:
        return {"window_s": self.window_s, "idle_s": self.idle_s,
                "covered_idle_s": self.covered_idle_s,
                "by_name": {n: dataclasses.asdict(s)
                            for n, s in sorted(self.by_name.items())},
                "gaps": [[g, [list(x) for x in under]]
                         for g, under in self.gaps]}


def event_name(name: str) -> Optional[str]:
    """The stage or ``py.gc`` name of a host event, else None."""
    name = name.split("#", 1)[0]
    return name if name.startswith(PREFIX) or name == GC else None


def _overlap(s: float, e: float, intervals, starts) -> float:
    """Length of ``[s, e]`` inside sorted disjoint ``intervals``."""
    total = 0.0
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    while i < len(intervals) and intervals[i][0] < e:
        lo, hi = max(s, intervals[i][0]), min(e, intervals[i][1])
        if hi > lo:
            total += hi - lo
        i += 1
    return total


def reduce(window: Tuple[float, float],
           host: Iterable[Tuple[str, float, float]],
           device_ops: Iterable[Tuple[float, float]],
           n_gaps: int = 10) -> Stages:
    """``window`` and every ``(name, start, end)`` host event and
    ``(start, end)`` device op in ns, on one clock."""
    w0, w1 = window
    _, busy = union_length([(max(s, w0), min(e, w1)) for s, e in device_ops
                            if e > w0 and s < w1])
    idle, prev = [], w0
    for s, e in busy + [[w1, w1]]:
        if s > prev:
            idle.append((prev, s))
        prev = max(prev, e)
    starts = [s for s, _ in idle]
    by_name: Dict[str, Stage] = {}
    clipped = []
    for raw, s, e in host:
        name = event_name(raw)
        if name is None or e <= w0 or s >= w1:
            continue
        s, e = max(s, w0), min(e, w1)
        st = by_name.setdefault(name, Stage())
        st.count += 1
        st.seconds += (e - s) / 1e9
        st.idle_s += _overlap(s, e, idle, starts) / 1e9
        clipped.append((name, s, e))
    _, under = union_length([(s, e) for _, s, e in clipped])
    under_starts = [s for s, _ in under]
    covered = sum(_overlap(s, e, under, under_starts) for s, e in idle)
    gaps = []
    for s, e in sorted(idle, key=lambda g: g[0] - g[1])[:n_gaps]:
        names: Dict[str, float] = {}
        for name, hs, he in clipped:
            o = min(e, he) - max(s, hs)
            if o > 0:
                names[name] = names.get(name, 0.0) + o / 1e9
        gaps.append(((e - s) / 1e9,
                     sorted(names.items(), key=lambda x: -x[1])))
    return Stages(window_s=(w1 - w0) / 1e9,
                  idle_s=sum(e - s for s, e in idle) / 1e9,
                  covered_idle_s=covered / 1e9, by_name=by_name, gaps=gaps)


def summarize(log_dir, window_name: str = "bench.window") -> Optional[Stages]:
    """Reduce the trace under ``log_dir``; None where it holds no window
    or no TPU plane."""
    from jax.profiler import ProfileData

    path = find_xplane(log_dir)
    if path is None:
        return None
    pd = ProfileData.from_file(str(path))
    host, window, device = [], None, None
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == window_name and window is None:
                        window = (e.start_ns, e.end_ns)
                    elif event_name(e.name) is not None:
                        host.append((e.name, e.start_ns, e.end_ns))
        elif plane.name.startswith("/device:TPU:") and device is None:
            device = [(e.start_ns, e.end_ns) for line in plane.lines
                      if line.name == "XLA Ops" for e in line.events]
    if window is None or device is None:
        return None
    return reduce(window, host, device)


# -- the readings --------------------------------------------------------------

def _per(total_s: float, count: int) -> Optional[float]:
    return 1e3 * total_s / count if count > 0 else None


def dispatch_host_ms_per_batch(st: Optional[Stages]) -> Optional[float]:
    """Coalesce, route and enqueue seconds per enqueued micro-batch."""
    if st is None:
        return None
    return _per(sum(st.get(f"{PREFIX}{n}").seconds
                    for n in ("coalesce", "route", "enqueue")),
                st.get(f"{PREFIX}enqueue").count)


def harvest_host_ms_per_batch(st: Optional[Stages]) -> Optional[float]:
    """Account and fulfil seconds per accounted micro-batch."""
    if st is None:
        return None
    return _per(st.get(f"{PREFIX}account").seconds
                + st.get(f"{PREFIX}fulfil").seconds,
                st.get(f"{PREFIX}account").count)


def readback_idle_ms_per_step(st: Optional[Stages],
                              steps: int) -> Optional[float]:
    """Device idle under ``topk.await``, per step of the window."""
    if st is None or st.get(f"{PREFIX}await").count == 0:
        return None
    return _per(st.get(f"{PREFIX}await").idle_s, steps)


def enqueue_idle_ms_per_step(st: Optional[Stages],
                             steps: int) -> Optional[float]:
    """Device idle under ``topk.validate`` and ``topk.enqueue``, per
    step of the window."""
    if st is None or st.get(f"{PREFIX}enqueue").count == 0:
        return None
    return _per(st.get(f"{PREFIX}validate").idle_s
                + st.get(f"{PREFIX}enqueue").idle_s, steps)


def gc_pause_ms_per_s(st: Optional[Stages]) -> Optional[float]:
    """``py.gc`` milliseconds per second of the window. None in a trace
    with neither ``py.gc`` nor stage events: a program that marks
    neither, not one that never collected."""
    if st is None or not st.by_name or st.window_s <= 0:
        return None
    return 1e3 * st.get(GC).seconds / st.window_s
