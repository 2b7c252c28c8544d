"""Reduction of a profiler trace (``.xplane.pb``) to the benchmark's numbers.

``reduce_trace(path, spans)`` reads the file with ``jax.profiler.ProfileData``
and returns a :class:`TraceReduction`:

* ``busy_s`` — the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each device plane), averaged over devices;
* ``window_s`` — the traced window: from the first to the last of the
  harness's own host spans;
* ``programs`` — device seconds per compiled program (``XLA Modules`` line),
  with the ``(N)`` suffix XLA adds taken off;
* ``ops`` — device self seconds per operation name: an op's time less that
  of the ops nested inside it (a ``while`` or ``cond`` holds its body's);
* ``gaps`` — the idle intervals of the first device inside the window, each
  labelled with the harness span open on the host during most of it
  (``"none"`` where none was).

Only the harness's spans are read from the host: their names are passed in
as ``spans``.  JAX is imported inside the function, never at import time.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

_SUFFIX = re.compile(r"\(\d+\)$")


def op_name(hlo: str) -> str:
    """An XLA op's trace name shortened to its name and result type:
    ``%fusion.7 = f32[4,8]{1,0} fusion(...)`` -> ``%fusion.7 = f32[4,8]``."""
    return hlo.split("{", 1)[0].split(" fusion(", 1)[0].strip()


@dataclass
class TraceReduction:
    busy_s: float
    window_s: float
    programs: dict = field(default_factory=dict)
    ops: dict = field(default_factory=dict)
    gaps: list = field(default_factory=list)      # [(label, seconds)]
    n_devices: int = 0

    def idle_by_label(self) -> dict:
        out: dict = {}
        for label, s in self.gaps:
            out[label] = out.get(label, 0.0) + s
        return out


def union_s(intervals: list, lo: int, hi: int) -> float:
    """Seconds covered by the union of ``(start_ns, end_ns)`` intervals,
    clipped to ``[lo, hi]``."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def idle_gaps(intervals: list, lo: int, hi: int) -> list:
    """The ``(start_ns, end_ns)`` intervals of ``[lo, hi]`` no interval
    covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if e <= cur:
            continue
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def label_gaps(gaps: list, spans: list) -> list:
    """Label each gap with the span name that overlaps it most."""
    spans = sorted(spans, key=lambda x: x[1])
    starts = [s for _, s, _ in spans]
    out = []
    for g0, g1 in gaps:
        best, label = 0, "none"
        i = max(0, bisect.bisect_right(starts, g0) - 64)
        for name, s, e in spans[i:]:
            if s >= g1:
                break
            ov = min(e, g1) - max(s, g0)
            if ov > best:
                best, label = ov, name
        out.append((label, (g1 - g0) / 1e9))
    return out


def self_times(events: list) -> dict:
    """Seconds per name of ``(name, start_ns, end_ns)`` events, each less
    the events nested inside it."""
    out: dict = {}
    stack: list = []          # [name, end_ns] of the open enclosing events
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            parent = stack[-1][0]
            out[parent] = out.get(parent, 0.0) - (e - s) / 1e9
        out[name] = out.get(name, 0.0) + (e - s) / 1e9
        stack.append([name, e])
    return out


def _events(line):
    for ev in line.events:
        yield ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns)


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def reduce_trace(path: str, spans: tuple) -> TraceReduction:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [e for e in _events(line) if e[0] in spans]
        elif plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {line.name: line for line in plane.lines}
            if "XLA Ops" in lines:
                devices.append(lines)
    if not host:
        raise ValueError(f"no harness span {spans} in {path}")
    lo = min(s for _, s, _ in host)
    hi = max(e for _, _, e in host)
    programs, ops, busy = {}, {}, []
    gaps = []
    for k, lines in enumerate(devices):
        op_ev = list(_events(lines["XLA Ops"]))
        for name, t in self_times(op_ev).items():
            ops[op_name(name)] = ops.get(op_name(name), 0.0) + t
        op_iv = [(s, e) for _, s, e in op_ev]
        if "XLA Modules" in lines:
            for name, s, e in _events(lines["XLA Modules"]):
                name = _SUFFIX.sub("", name)
                programs[name] = programs.get(name, 0.0) + (e - s) / 1e9
        busy.append(union_s(op_iv, lo, hi))
        if k == 0:
            gaps = label_gaps(idle_gaps(op_iv, lo, hi), host)
    return TraceReduction(
        busy_s=sum(busy) / len(busy) if busy else 0.0,
        window_s=(hi - lo) / 1e9, programs=programs, ops=ops, gaps=gaps,
        n_devices=len(devices))
