"""The program's own spans on the trace clock, for the readers of idle
and compile time.

The program records the generation pump's state (``pump.step``,
``pump.wait``) and JAX's traces and compiles (``jit.*``) as complete
(``X``) events of its ``obs.Tracer``.  The harness binds that tracer to
the engine for the traced part of the window and records one marker
(``trace.MARKER``) on both clocks.  The tracer is found through the
engines attached to ``repro.obs.jitlog`` (the one that holds the
marker); a program without ``jitlog`` or without the marker gives
``None``, and so does every reader built on it.

Intervals are ``(start_ns, end_ns)`` on the trace clock.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

from lib import trace as T

Span = Tuple[float, float, str, int]      # start_ns, end_ns, name, tid


def _bound_tracer():
    try:
        from repro.obs import jitlog
    except ImportError:
        return None
    for sink in jitlog.sinks():
        tr = getattr(sink, "tracer", None)
        if tr is not None and tr.enabled and any(
                e[1] == T.MARKER for e in tr.events()):
            return tr
    return None


def complete_spans(events, marker_ns: float) -> List[Span]:
    """The complete (``X``) events of a tracer's ring (``events()``) on
    the trace clock, given the marker's time there."""
    mark_us = next((e[2] for e in events if e[1] == T.MARKER), None)
    if mark_us is None:
        return []
    out = []
    for ph, name, ts_us, tid, dur_us, _attrs in events:
        if ph == "X":
            s = marker_ns + (ts_us - mark_us) * 1e3
            out.append((s, s + dur_us * 1e3, name, tid))
    return out


def host_spans(ctx) -> Optional[List[Span]]:
    """The bound tracer's complete events on the trace clock, or None."""
    tr = ctx["trace"]
    if not tr or tr["reduced"].marker_ns is None:
        return None
    tracer = _bound_tracer()
    if tracer is None:
        return None
    return complete_spans(tracer.events(), tr["reduced"].marker_ns)


def window_ns(red) -> Tuple[float, float]:
    """The traced span on the trace clock (it starts at the marker)."""
    return red.marker_ns, red.marker_ns + red.window_s * 1e9


def covered(gaps: Sequence[Tuple[float, float]],
            spans: Sequence[Tuple[float, float]]) -> float:
    """Length of the part of ``gaps`` (disjoint) that lies inside the
    union of ``spans`` (which may overlap or nest)."""
    _, merged = T.union_length(spans)
    total, j = 0.0, 0
    for gs, ge in sorted(gaps):
        while j < len(merged) and merged[j][1] <= gs:
            j += 1
        k = j
        while k < len(merged) and merged[k][0] < ge:
            total += min(ge, merged[k][1]) - max(gs, merged[k][0])
            k += 1
    return total


def idle_share_in(ctx, name: str) -> Optional[float]:
    """Device-0 idle time inside the program's ``name`` spans, in
    percent of the traced span; None where the program records no
    ``pump.*`` spans at all."""
    spans = host_spans(ctx)
    if spans is None or not any(n.startswith("pump.")
                                for _, _, n, _ in spans):
        return None
    red = ctx["trace"]["reduced"]
    if red.window_s <= 0:
        return None
    mine = [(s, e) for s, e, n, _ in spans if n == name]
    return 100.0 * covered(red.gaps, mine) * 1e-9 / red.window_s
