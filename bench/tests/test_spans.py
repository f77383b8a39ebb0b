"""The readers of idle and compile time, on hand-made device gaps and
program spans: the two idle shares are disjoint parts of the idle time,
and compile time is the union of nested and overlapping ``jit.*`` spans
clipped to the traced span, not their sum."""
import threading
from types import SimpleNamespace

import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
from lib import spans as SP
from lib import spec
from lib import trace as T
from repro.obs import NULL_REGISTRY, Tracer, jitlog

M = 5e12                     # the marker on the trace clock, ns
S = 1e9                      # ns per second


class _Sink:
    """What the engine is to ``jitlog``: a tracer and a registry."""

    def __init__(self, tracer):
        self.tracer, self.registry = tracer, NULL_REGISTRY


def _ctx(tracer_events):
    """A traced span of 1 s from the marker; device-0 idle in
    [0, .2], [.25, .5] and [.9, 1] s (55 %).  ``tracer_events`` records
    the program's spans, in seconds after the marker."""
    red = T.Reduced(window_s=1.0, busy_s=0.45, devices=1,
                    gaps=[(M + .25 * S, M + .5 * S), (M, M + .2 * S),
                          (M + .9 * S, M + 1.0 * S)], marker_ns=M)
    tr = Tracer()
    tr.instant(T.MARKER)
    mark = tr._t0 + tr.events()[0][2] * 1e-6
    tracer_events(lambda name, a, b, **kw: tr.complete(
        name, mark + a, mark + b, **kw))
    sink = _Sink(tr)
    jitlog.attach(sink)
    return {"trace": {"reduced": red, "window_s": 1.0, "busy_s": 0.45},
            "requests": []}, sink


def _read(name, ctx):
    return spec.metric_reader(name).read(ctx)


@pytest.fixture
def traced():
    def record(x):
        x("pump.wait", 0.1, 0.3)
        x("pump.step", 0.3, 0.95)
        x("jit.trace", -0.5, -0.1, fun="before")     # outside the span
        x("jit.trace", 0.05, 0.4, fun="outer")
        x("jit.trace", 0.1, 0.2, fun="inner")        # nested
        x("jit.trace", 0.95, 1.3, fun="late")        # clipped at 1 s

        def other():                                 # another thread
            x("jit.compile", 0.35, 0.6, fun="outer")
        th = threading.Thread(target=other)
        th.start()
        th.join(timeout=10.0)
        assert not th.is_alive()
    ctx, sink = _ctx(record)
    yield ctx
    jitlog.detach(sink)


def test_idle_shares_split_the_idle_time(traced):
    starved = _read("idle_starved_share", traced)
    in_step = _read("idle_in_step_share", traced)
    # gaps inside [.1, .3]: .1 + .05 s; inside [.3, .95]: .2 + .05 s
    assert starved == pytest.approx(15.0)
    assert in_step == pytest.approx(25.0)
    assert starved + in_step <= _read("idle_share", dict(
        traced, streamed_bytes=0)) + 1e-9


def test_compile_share_is_the_clipped_union(traced):
    # union of [.05, .4], [.1, .2], [.35, .6] is [.05, .6]; [.95, 1.3]
    # adds .05 inside the span; the sum would read 105 %
    assert _read("compile_share", traced) == pytest.approx(60.0)


def test_covered_merges_overlapping_spans():
    gaps = [(0, 10), (20, 30), (40, 50)]
    spans = [(5, 25), (8, 22), (45, 60), (60, 70), (31, 39)]
    assert SP.covered(gaps, spans) == (5 + 5 + 5)
    assert SP.covered(gaps, []) == 0
    assert SP.covered([], spans) == 0


def test_no_program_spans_reads_nothing():
    """A program that records no pump spans, or no marker, or a run
    without a trace, gives no reading and raises nothing."""
    ctx, sink = _ctx(lambda x: x("jit.trace", 0.1, 0.2))
    try:
        assert _read("idle_starved_share", ctx) is None
        assert _read("idle_in_step_share", ctx) is None
        assert _read("compile_share", ctx) == pytest.approx(10.0)
    finally:
        jitlog.detach(sink)
    assert _read("idle_starved_share", ctx) is None     # no tracer found
    assert _read("compile_share", ctx) is None
    none = {"trace": None, "requests": []}
    for name in ("idle_starved_share", "idle_in_step_share",
                 "compile_share", "first_token_p50_s"):
        assert _read(name, none) is None


def test_first_token_median():
    def req(start, first):
        return SimpleNamespace(t_gen_start=start, t_first_token=first)
    reqs = [req(1.0, 1.5), req(2.0, 2.1), req(3.0, 3.9),
            req(4.0, None),                      # not stamped
            SimpleNamespace(t_gen_start=5.0)]    # a program without it
    assert _read("first_token_p50_s", {"requests": reqs}) == \
        pytest.approx(0.5)
    assert _read("first_token_p50_s", {"requests": reqs[3:]}) is None
