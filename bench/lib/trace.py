"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

* busy time: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
  clipped to the traced window and averaged over the devices;
* per-operation device time and call count, by operation name, with the
  event's HLO text kept for kernel matching;
* idle gaps: the holes in that union, longest first.

The host clock of the program's own spans is tied to the trace's clock
by one marker that the harness records on both (``MARKER``).
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

MARKER = "bench.clock"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
# stats that name what an op is; kept for matching kernels by name
_NAME_STATS = ("long_name", "hlo_op", "tf_op", "kernel_details",
               "source_info")


@dataclass
class OpTotal:
    seconds: float = 0.0
    count: int = 0
    text: str = ""          # name + naming stats of the first event seen


@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # averaged over devices
    devices: int
    ops: Dict[str, OpTotal] = field(default_factory=dict)
    gaps: List[Tuple[float, float]] = field(default_factory=list)
    marker_ns: Optional[float] = None   # marker start on the trace clock


def union_length(intervals: Sequence[Tuple[float, float]]
                 ) -> Tuple[float, List[Tuple[float, float]]]:
    """Length of the union of ``(start, end)`` intervals, and the merged
    intervals in order."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def gaps_of(merged: Sequence[Tuple[float, float]], lo: float, hi: float
            ) -> List[Tuple[float, float]]:
    """Holes of ``merged`` (sorted, disjoint) inside ``[lo, hi]``."""
    out, cur = [], lo
    for s, e in merged:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def find(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _stats_text(ev) -> str:
    try:
        stats = dict(ev.stats)
    except Exception:
        return ""
    return " ".join(f"{k}={stats[k]}" for k in _NAME_STATS if k in stats)


def reduce(path: str, window_ns: Optional[Tuple[float, float]] = None,
           after_marker: Optional[Tuple[float, float]] = None,
           device_prefix: str = DEVICE_PREFIX) -> Reduced:
    """Reduce the trace at ``path`` over a window on the trace clock:
    ``window_ns``, or ``after_marker = (start_s, end_s)`` seconds after
    the marker, or else the span of the device events."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    marker = None
    per_dev: List[List[Tuple[float, float, str, object]]] = []
    for plane in pd.planes:
        if plane.name.startswith(device_prefix) and \
                plane.name[len(device_prefix):].isdigit():
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = float(ev.start_ns)
                    evs.append((s, s + float(ev.duration_ns), ev.name, ev))
            per_dev.append(evs)
        elif marker is None and not plane.name.startswith("/device"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == MARKER:
                        marker = float(ev.start_ns)
                        break
                if marker is not None:
                    break
    if not per_dev:
        raise ValueError(f"no {device_prefix}* plane with an "
                         f"{OPS_LINE!r} line in {path}")
    if after_marker is not None:
        if marker is None:
            raise ValueError(f"no {MARKER!r} event in {path}")
        window_ns = (marker + after_marker[0] * 1e9,
                     marker + after_marker[1] * 1e9)
    if window_ns is None:
        starts = [e[0] for evs in per_dev for e in evs]
        ends = [e[1] for evs in per_dev for e in evs]
        window_ns = (min(starts), max(ends)) if starts else (0.0, 0.0)
    lo, hi = window_ns
    ops: Dict[str, OpTotal] = {}
    busy_total = 0.0
    gaps: List[Tuple[float, float]] = []
    for d, evs in enumerate(per_dev):
        clipped = []
        for s, e, name, ev in evs:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            clipped.append((s, e))
            tot = ops.get(name)
            if tot is None:
                tot = ops[name] = OpTotal(text=f"{name} {_stats_text(ev)}")
            tot.seconds += (e - s) * 1e-9
            tot.count += 1
        busy, merged = union_length(clipped)
        busy_total += busy
        if d == 0:
            gaps = gaps_of(merged, lo, hi)
    gaps.sort(key=lambda g: g[0] - g[1])
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=busy_total * 1e-9 / len(per_dev),
                   devices=len(per_dev), ops=ops, gaps=gaps,
                   marker_ns=marker)


def kernel_time(red: Reduced, pattern: str) -> Tuple[float, int]:
    """Device seconds and calls of every op whose HLO text matches the
    regular expression ``pattern``."""
    secs, calls = 0.0, 0
    rx = re.compile(pattern)
    for tot in red.ops.values():
        if rx.search(tot.text):
            secs += tot.seconds
            calls += tot.count
    return secs, calls


def top_ops(red: Reduced, n: int = 10) -> List[List]:
    items = sorted(red.ops.items(), key=lambda kv: -kv[1].seconds)[:n]
    return [[name, tot.seconds] for name, tot in items]


def label_gaps(red: Reduced, spans: Sequence[Tuple[float, float, str]],
               n: int = 10) -> List[List]:
    """The ``n`` longest idle gaps, each named by the host span (trace
    clock ``(start_ns, end_ns, name)``) that overlaps it most, or
    ``"no span"``."""
    out = []
    for s, e in red.gaps[:n]:
        best, name = 0.0, "no span"
        for a, b, nm in spans:
            ov = min(b, e) - max(a, s)
            if ov > best:
                best, name = ov, nm
        out.append([name, (e - s) * 1e-9])
    return out
