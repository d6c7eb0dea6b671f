"""The program's spans read beside the harness: host gap between device
launches, wall queue wait (censored at the window's end), collector
pauses, and device-idle gaps named by the program span open at their
midpoint."""
import json
import os
from types import SimpleNamespace as NS

import pytest

from chipbench import spans

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_spans.json")


def _s(name, t0, t1, **kw):
    return dict(kind="span", name=name, t0_s=t0, t1_s=t1, clock="wall",
                **kw)


def _run(records, seconds=10.0):
    # a program with host phases always records its steps
    return NS(seconds=seconds, spans=[_s("sched.step", 0.0, seconds)]
              + records)


def test_host_gap_pairs_each_fetch_with_the_next_dispatch():
    run = _run([
        _s("decode.dispatch", 1.000, 1.001), _s("decode.fetch", 1.001, 1.030),
        # next launch: a prefill after retire; its first dispatch counts
        _s("prefill.dispatch", 1.034, 1.035),
        _s("prefill.dispatch", 1.036, 1.040),
        _s("prefill.fetch", 1.040, 1.100),
        _s("decode.dispatch", 1.102, 1.104), _s("decode.fetch", 1.104, 1.13),
        # a fetch with no launch after it pairs with nothing
    ])
    # gaps: 1.030 -> 1.035, 1.100 -> 1.104
    assert spans.host_gap_ms(run) == pytest.approx(1e3 * (0.005 + 0.004) / 2)
    assert spans.host_gaps(run.spans, 1.05, 1.2) == \
        [pytest.approx(0.004)]
    # a launch that ends after the window is not in it
    late = _run([_s("decode.fetch", 9.0, 9.99),
                 _s("decode.dispatch", 10.0, 10.01)])
    assert spans.host_gap_ms(late) is None


def test_queue_span_p90_censors_requests_not_started():
    recs = []
    for rid, (sub, start) in enumerate([(1.0, 1.5), (2.0, 2.5), (3.0, 3.5),
                                        (4.0, None), (8.0, 12.0)]):
        recs.append(_s("admit", sub, sub, request_id=rid))
        if start is not None:
            recs.append(_s("queue", sub, start, request_id=rid))
    # before the window: not submitted in it
    recs.append(_s("admit", -1.0, -1.0, request_id=9))
    recs.append(_s("queue", -1.0, 0.5, request_id=9))
    run = _run(recs)
    # waits 0.5, 0.5, 0.5, then 6.0 and 2.0 censored at 10 s
    vals = sorted([0.5, 0.5, 0.5, 6.0, 2.0])
    want = vals[3] + (vals[4] - vals[3]) * 0.6
    assert spans.queue_span_p(run, 90) == pytest.approx(1e3 * want)
    # a preempted request queues twice: its first start counts
    recs.append(_s("queue", 1.0, 9.0, request_id=0))
    assert spans.queue_span_p(_run(recs), 90) == pytest.approx(1e3 * want)


def test_gc_pause_sums_pauses_that_end_in_the_window():
    run = _run([_s("gc", -0.5, -0.4), _s("gc", 1.0, 1.002),
                _s("gc", 5.0, 5.25), _s("gc", 9.99, 10.5)])
    assert spans.gc_pause_ms(run) == pytest.approx(252.0)
    assert spans.gc_pause_ms(_run([])) == 0.0     # traced, no pause


@pytest.mark.parametrize("read", [spans.host_gap_ms, spans.gc_pause_ms,
                                  lambda r: spans.queue_span_p(r, 90)])
def test_readers_return_nothing_without_program_spans(read):
    # a program without host phases: none, or only its old wall spans
    assert read(NS(seconds=5.0)) is None
    assert read(NS(seconds=5.0, spans=None)) is None
    assert read(NS(seconds=5.0, spans=[_s("decode", 1.0, 1.1),
                                       _s("prefill", 0.5, 0.9)])) is None


def test_window_spans_keeps_wall_spans_shifted_to_the_window():
    recs = [_s("decode", 101.0, 101.5),
            dict(kind="span", name="queue", t0_s=0.0, t1_s=3.0,
                 clock="sim")]
    assert spans.window_spans(recs, 100.0) == [_s("decode", 1.0, 1.5)]


def test_gaps_named_by_the_program_span_at_their_midpoint():
    with open(FIXTURE) as f:
        t = json.load(f)
    named = spans.named_gaps(t, t["program"])
    assert [[n, pytest.approx(g)] for n, g, _ in named] == [
        # starts after the harness's decode_step closed; the host retires
        ["host idle/sched.retire", 1250e-9],
        # starts in the fetch that waited, the host is dispatching at
        # its midpoint (at its start it would read decode.fetch)
        ["decode_step/decode.dispatch", 950e-9],
        # the harness's own time between steps: no program span
        ["start_batch", 700e-9],
    ]
    assert spans.innermost(t["program"], 1000) == "decode.fetch"


def test_program_spans_reach_the_profiler_as_repro_events(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.obs import Tracer
    tr = Tracer()
    f = jax.jit(lambda x: x + 1)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("sched.step"):
            with tr.span("decode", step_num=7):
                with tr.span("decode.dispatch"):
                    y = f(jnp.ones(4))
                with tr.span("decode.fetch"):
                    y.block_until_ready()
    finally:
        jax.profiler.stop_trace()
        tr.close()
    events = spans.program_events(str(tmp_path))
    names = {n for n, _, _ in events}
    assert {"repro/sched.step", "repro/decode", "repro/decode.dispatch",
            "repro/decode.fetch"} <= names
    ev = {n: (s, d) for n, s, d in events}
    s, d = ev["repro/decode.fetch"]
    assert spans.innermost(events, s + d / 2) == "decode.fetch"
    # the profiler's interval and the tracer's agree on the span's length
    wall = {x.name: x.t1_s - x.t0_s for x in tr.spans}
    s, d = ev["repro/decode"]
    assert d * 1e-9 == pytest.approx(wall["decode"], abs=2e-3)
