"""Observability layer (span tracing + metrics + profiling hooks).

Covers the ISSUE 7 contract:
* metrics registry semantics — counters/gauges/histograms with labels,
  Prometheus text exposition (exact output + escaping), JSON snapshots,
  registration conflicts, null no-ops (hypothesis-gated histogram
  invariants with deterministic companions);
* span tracer — parent/child request structure, batch context, TraceStore
  mirroring and round-trip, lifecycle reconstruction;
* TraceStore hardening — non-finite values and unknown kinds rejected,
  mixed-kind save/load round-trip;
* pipeline integration — admission reason codes, queue depth, occupancy /
  queue-delay observation, per-request ``queue_delay_s`` on BatchRecord
  entries (stub backend/router, no JAX in the loop);
* the pinned guarantee: serving output is bit-identical with the full
  observability stack on vs off (real tiny model, scheduler and engine
  paths);
* benchmarks/compare.py regression detection and the profile entry point's
  fitter-compatible kernel records.
"""
import json
import math
import os
import sys

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.obs import (DEFAULT_BUCKETS, LIFECYCLE, MetricsRegistry, NULL_OBS,
                       NullRegistry, NullTracer, Observability, Tracer,
                       lifecycles_complete, make_observability,
                       reconstruct_lifecycles)
from repro.obs.metrics import PeriodicReporter

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


# ============================================================ metrics: core

def test_counter_labels_and_negative_rejected():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests", labelnames=("tier",))
    c.inc(tier="interactive")
    c.inc(2, tier="economy")
    c.inc(tier="economy")
    assert c.value(tier="interactive") == 1
    assert c.value(tier="economy") == 3
    assert c.value(tier="never") == 0
    with pytest.raises(ValueError):
        c.inc(-1, tier="economy")
    with pytest.raises(ValueError):
        c.inc()                      # missing label


def test_gauge_set_max_tracks_high_water():
    reg = MetricsRegistry()
    g = reg.gauge("blocks", "in use")
    g.set(4)
    g.inc(2)
    g.dec()
    assert g.value() == 5
    hw = reg.gauge("blocks_hw", "high water")
    for v in (3, 9, 5):
        hw.set_max(v)
    assert hw.value() == 9


def test_histogram_deterministic_counts_and_quantile():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "latency", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.5, 3.0, 100.0):
        h.observe(v)
    assert h.bucket_counts() == [1, 2, 1, 1]          # last bin = overflow
    assert h.cumulative_counts() == [1, 3, 4, 5]
    assert h.total() == 5
    assert h.sum_value() == pytest.approx(106.5)
    # median falls in the (1, 2] bucket; overflow quantiles clamp to the
    # largest finite edge
    assert 1.0 <= h.quantile(0.5) <= 2.0
    assert h.quantile(1.0) == 4.0
    assert math.isnan(reg.histogram("empty", "e").quantile(0.5))


@settings(max_examples=40, deadline=None)
@given(st.lists(st.floats(min_value=0.0, max_value=50.0,
                          allow_nan=False), min_size=1, max_size=60),
       st.floats(min_value=0.01, max_value=0.99))
def test_histogram_invariants_hypothesis(values, q):
    h = MetricsRegistry().histogram("h", "h", buckets=(0.1, 1.0, 10.0))
    for v in values:
        h.observe(v)
    cum = h.cumulative_counts()
    assert cum == sorted(cum)                       # monotone
    assert cum[-1] == len(values) == h.total()      # +Inf catches all
    assert h.sum_value() == pytest.approx(sum(values))
    assert 0.0 <= h.quantile(q) <= 10.0             # bounded by finite edges


# ====================================================== metrics: exposition

def test_prometheus_text_exact():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", "served requests", labelnames=("tier",))
    c.inc(3, tier="a")
    g = reg.gauge("depth", "queue depth")
    g.set(2.5)
    want = (
        "# HELP depth queue depth\n"
        "# TYPE depth gauge\n"
        "depth 2.5\n"
        "# HELP reqs_total served requests\n"
        "# TYPE reqs_total counter\n"
        'reqs_total{tier="a"} 3\n'
    )
    assert reg.to_prometheus() == want


def test_prometheus_histogram_exposition_and_escaping():
    reg = MetricsRegistry()
    h = reg.histogram("lat", "latency", labelnames=("op",),
                      buckets=(1.0, 2.0))
    h.observe(0.5, op='we"ird\\na\nme')
    h.observe(5.0, op='we"ird\\na\nme')
    text = reg.to_prometheus()
    assert '\\"' in text and "\\\\" in text and "\\n" in text
    assert 'le="+Inf"' in text
    assert "lat_sum" in text and "lat_count" in text
    # cumulative: the +Inf bucket equals _count
    inf_line = [l for l in text.splitlines() if 'le="+Inf"' in l][0]
    count_line = [l for l in text.splitlines()
                  if l.startswith("lat_count")][0]
    assert inf_line.rsplit(" ", 1)[1] == count_line.rsplit(" ", 1)[1] == "2"


def test_registry_conflicts_and_reuse():
    reg = MetricsRegistry()
    c1 = reg.counter("x_total", "x")
    assert reg.counter("x_total", "x") is c1        # get-or-create
    with pytest.raises(ValueError):
        reg.gauge("x_total", "x")                   # type conflict
    with pytest.raises(ValueError):
        reg.counter("x_total", "x", labelnames=("t",))   # label conflict
    assert "x_total" in reg.names()


def test_null_registry_is_inert():
    reg = NullRegistry()
    assert not reg.enabled
    c = reg.counter("a_total", "a", labelnames=("t",))
    c.inc(tier_whatever="v")                        # labels unchecked
    g = reg.gauge("g", "g")
    g.set(3)
    g.set_max(9)
    h = reg.histogram("h", "h")
    h.observe(1.0)
    with pytest.raises(RuntimeError):
        reg.write("/tmp/nope.json")


def test_registry_write_and_periodic_reporter(tmp_path):
    reg = MetricsRegistry()
    reg.counter("n_total", "n").inc(7)
    path = str(tmp_path / "m.json")
    reporter = PeriodicReporter(reg, path, interval_s=10.0)
    assert reporter.maybe_write(0.0)                # first call writes
    assert not reporter.maybe_write(5.0)            # within interval
    assert reporter.maybe_write(11.0)
    snap = json.load(open(path))
    assert snap["n_total"]["values"][0]["value"] == 7
    prom = open(str(tmp_path / "m.prom")).read()
    assert "n_total 7\n" in prom


# ================================================================== tracer

def test_tracer_parenting_and_batch_context():
    tr = Tracer()
    root = tr.emit("admit", 0.0, request_id=5, tier="standard")
    tr.batch_context = 3
    child = tr.emit("queue", 0.0, 1.0, request_id=5)
    recs = tr.records()
    assert len(tr) == 2
    assert recs[0]["kind"] == "span" and recs[0]["name"] == "admit"
    assert recs[1]["parent_id"] == root
    assert recs[1]["batch_id"] == 3                 # from batch_context
    assert child != root


def test_tracer_mirrors_into_store_and_roundtrips(tmp_path):
    from repro.qeil2 import TraceStore
    store = TraceStore()
    tr = Tracer(store=store)
    tr.emit("admit", 0.0, request_id=0)
    tr.emit("release", 0.0, 2.0, request_id=0, clock="sim")
    assert len(store.records("span")) == 2
    p = str(tmp_path / "t.jsonl")
    store.save(p)
    back = TraceStore(path=p)
    assert [r["name"] for r in back.records("span")] == ["admit", "release"]


def test_null_tracer_is_inert():
    tr = NullTracer()
    assert not tr.enabled
    assert tr.emit("admit", 0.0) == -1
    assert len(tr) == 0
    with pytest.raises(RuntimeError):
        tr.save("/tmp/nope.jsonl")


def test_lifecycle_reconstruction_complete_and_incomplete():
    tr = Tracer()
    tr.emit("admit", 0.0, request_id=0, admitted=True)
    tr.batch_context = 0
    tr.emit("schedule", 1.0, 2.0)
    tr.emit("prefill", 0.0, 0.1, clock="wall")
    tr.emit("decode", 0.1, 0.2, clock="wall", step=0)
    tr.emit("queue", 0.0, 1.0, request_id=0)
    tr.emit("release", 2.0, 2.0, request_id=0, latency_s=2.0)
    life = reconstruct_lifecycles(tr.spans)
    assert life[0]["complete"] and life[0]["missing"] == []
    assert lifecycles_complete(tr.spans, expect_requests=1)
    # a second request that never releases is incomplete
    tr.emit("admit", 3.0, request_id=1, admitted=True)
    tr.emit("queue", 3.0, 4.0, request_id=1)
    life = reconstruct_lifecycles(tr.spans)
    assert not life[1]["complete"] and "release" in life[1]["missing"]
    assert not lifecycles_complete(tr.spans, expect_requests=2)


def test_tracer_span_nesting_parent_ids_and_wall_times():
    import time
    tr = Tracer()
    before = time.perf_counter()
    with tr.span("outer", plen=8) as outer:
        with tr.span("inner", step_num=3):
            pass
        point = tr.emit("mark", time.perf_counter(), clock="wall")
        outer.set(done=True)
    after = time.perf_counter()
    by = {s.name: s for s in tr.spans}
    assert [s.name for s in tr.spans] == ["inner", "mark", "outer"]
    o, i = by["outer"], by["inner"]
    assert o.parent_id is None
    assert i.parent_id == o.span_id and by["mark"].parent_id == o.span_id
    assert point == by["mark"].span_id
    assert all(s.clock == "wall" for s in tr.spans)
    assert before <= o.t0_s <= i.t0_s <= i.t1_s <= o.t1_s <= after
    assert o.attrs == {"plen": 8, "done": True}
    assert i.attrs == {"step_num": 3}
    assert tr.opened_at("inner") == i.t0_s
    tr.close()


def test_null_tracer_span_records_and_opens_nothing(monkeypatch):
    import jax

    from repro.obs import tracer as tracer_mod

    def forbidden(*a, **k):
        raise AssertionError("a disabled span read a clock or annotated")

    monkeypatch.setattr(tracer_mod.time, "perf_counter", forbidden)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", forbidden)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", forbidden)
    tr = NullTracer()
    a = tr.span("decode", step_num=1, n_sequences=4)
    with a as sp:
        sp.set(prefill_tokens=3)
        with tr.span("decode.fetch") as inner:
            assert inner is sp
    assert a is NULL_OBS.tracer.span("prefill")      # one shared context
    assert len(tr) == 0 and tr.spans == [] and tr.opened_at("decode") is None


def test_gc_inside_span_emits_gc_child():
    import gc
    tr = Tracer()
    with tr.span("sched.retire"):
        gc.collect()
    [ret] = [s for s in tr.spans if s.name == "sched.retire"]
    pauses = [s for s in tr.spans if s.name == "gc"]
    full = [s for s in pauses if s.attrs["generation"] == 2]
    assert full and all(s.parent_id == ret.span_id for s in pauses)
    assert all(ret.t0_s <= s.t0_s <= s.t1_s <= ret.t1_s for s in pauses)
    assert isinstance(full[0].attrs["collected"], int)
    tr.close()


def test_jit_compile_inside_span_emits_compile_child():
    import jax
    import jax.numpy as jnp
    tr = Tracer()
    with tr.span("decode", n_sequences=5):
        with tr.span("decode.dispatch"):
            jax.jit(lambda x: x * 7 - 2)(jnp.ones(13)).block_until_ready()
    by = {s.name: s for s in tr.spans if s.name != "compile"}
    comp = [s for s in tr.spans if s.name == "compile"
            and s.attrs["fun_name"] == "jit(<lambda>)"]
    assert comp
    c = comp[0]
    assert c.parent_id == by["decode.dispatch"].span_id
    assert c.attrs["n_sequences"] == 5        # the open spans' shape attrs
    assert by["decode.dispatch"].t0_s <= c.t0_s <= c.t1_s \
        <= by["decode.dispatch"].t1_s
    tr.close()


@pytest.mark.parametrize("how", ["close", "collect"])
def test_tracer_hooks_go_with_close_or_collection(how):
    import gc

    from jax._src import monitoring
    n_gc = len(gc.callbacks)
    n_dur = len(monitoring._event_duration_secs_listeners)
    tr = Tracer()
    assert len(gc.callbacks) == n_gc + 1
    assert len(monitoring._event_duration_secs_listeners) == n_dur + 1
    if how == "close":
        tr.close()
        tr.close()                                   # idempotent
    else:
        del tr
        gc.collect()
    assert len(gc.callbacks) == n_gc
    assert len(monitoring._event_duration_secs_listeners) == n_dur


# ==================================================== TraceStore hardening

def test_tracestore_rejects_nonfinite_and_unknown_kind():
    from repro.qeil2 import TraceStore
    store = TraceStore()
    with pytest.raises(ValueError, match="non-finite"):
        store.ingest({"kind": "span", "name": "x", "t0_s": float("nan"),
                      "t1_s": 1.0})
    with pytest.raises(ValueError, match="non-finite"):
        store.ingest({"kind": "span", "name": "x", "t0_s": 0.0, "t1_s": 1.0,
                      "attrs": {"deep": [1.0, float("inf")]}})
    with pytest.raises(ValueError, match="unknown trace record kind"):
        store.ingest({"kind": "mystery", "name": "x"})
    assert len(store) == 0                          # nothing leaked in


def test_tracestore_mixed_kind_roundtrip(tmp_path):
    from repro.qeil2 import TraceStore
    store = TraceStore()
    store.ingest({"kind": "kernel", "kernel": "flash_attention", "flops": 1.0,
                  "bytes": 2.0, "measured_us": 3.0, "roofline_us": 0.5,
                  "quant": "int8"})
    store.ingest({"kind": "span", "name": "admit", "t0_s": 0.0, "t1_s": 0.0,
                  "request_id": 0})
    p = str(tmp_path / "mixed.jsonl")
    store.save(p)
    back = TraceStore(path=p)
    assert len(back.records("kernel")) == 1
    assert len(back.records("span")) == 1
    assert back.records("kernel")[0]["quant"] == "int8"


# ================================================ pipeline (stub) integration

from types import SimpleNamespace

from repro.qeil2 import SLATier, merge_tiers
from repro.serving import (ContinuousBatchingScheduler, RequestQueue,
                           SchedulerConfig)


class _StubHandle:
    def __init__(self, prompts, repeats, max_new):
        self.prompts = prompts
        self.repeats = repeats
        self.plen = len(prompts[0])
        self.steps_left = max_new - 1

    @property
    def n_sequences(self):
        return sum(self.repeats)

    @property
    def done(self):
        return self.steps_left <= 0


class _StubBackend:
    def __init__(self):
        self.slots_in_use = 0

    slots_free = None

    def note_placement(self, placement):
        pass

    def start_batch(self, prompts, n_samples, max_new, temperature, rng,
                    extras=None):
        h = _StubHandle(list(prompts), list(n_samples), max_new)
        self.slots_in_use += h.n_sequences
        return h

    def decode_step(self, h):
        h.steps_left -= 1
        return not h.done

    def finalize(self, h):
        self.slots_in_use -= h.n_sequences
        return [SimpleNamespace(prompt=p, samples=[], logprobs=[])
                for p in h.prompts]


class _StubRouter:
    def __init__(self, tiers):
        self.tiers = {t.name: t for t in tiers}

    def resolve_tier(self, tier):
        return self.tiers[tier] if isinstance(tier, str) else tier

    def required_samples(self, tier):
        return None

    def route_batch(self, tiers, **kw):
        members = [self.resolve_tier(t) for t in tiers]
        return SimpleNamespace(
            tier=merge_tiers(members), tier_counts={},
            assignment=object(), point_index=0, meets_caps=True,
            batch_costs=None, energy_j=1.0 * len(members),
            latency_s=0.5, notes=[],
            per_tier_energy_j={members[0].name: 1.0 * len(members)})


def _tiers3():
    return [SLATier("interactive", energy_weight=0.0, latency_weight=1.0),
            SLATier("standard", energy_weight=0.5, latency_weight=0.5),
            SLATier("economy", energy_weight=1.0, latency_weight=0.0)]


def test_scheduler_metrics_spans_and_queue_delay_entries():
    obs = make_observability()
    sched = ContinuousBatchingScheduler(
        _StubBackend(), _StubRouter(_tiers3()),
        SchedulerConfig(max_batch_requests=4, max_new_tokens=3), obs=obs)
    sched.advance_to(1.0)          # arrivals in the past: positive delays
    for i in range(3):
        adm = sched.submit(np.arange(1, 5, dtype=np.int32), tier="economy",
                           n_samples=1, arrival_s=0.1 * i)
        assert adm.admitted
    sched.on_reorchestrate()
    sched.run_until_idle()

    reg = obs.metrics
    adm_c = reg.get("serving_admission_total")
    assert adm_c.value(outcome="admitted", reason="ok") == 3
    assert reg.get("serving_queue_depth").value(tier="economy") == 0
    occ = reg.get("serving_batch_occupancy")
    assert occ.total() == 1 and occ.sum_value() == 3    # one 3-request batch
    assert reg.get("serving_queue_delay_s").total(tier="economy") == 3
    assert reg.get("serving_energy_j_total").value(tier="economy") == 3.0
    assert reg.get("serving_requests_completed_total").value(
        tier="economy") == 3
    assert reg.get("serving_reanneal_boundaries_total").value() == 1

    # per-request queue delay rides on the batch record (satellite c)
    [rec] = list(sched.records)
    assert len(rec.request_entries) == 3
    for e in rec.request_entries:
        assert e["queue_delay_s"] >= 0.0 and e["tier"] == "economy"

    # stub backends emit no prefill/decode wall spans; the scheduler-side
    # lifecycle (admit -> queue -> schedule -> release) must still be there
    names = {s.name for s in obs.tracer.spans}
    assert {"admit", "queue", "schedule", "release"} <= names
    per_req = {s.request_id for s in obs.tracer.spans if s.name == "release"}
    assert per_req == {0, 1, 2}


def test_scheduler_lifecycle_spans_are_wall_clock_and_keep_sim_values():
    import time
    obs = make_observability()
    sched = ContinuousBatchingScheduler(
        _StubBackend(), _StubRouter(_tiers3()),
        SchedulerConfig(max_batch_requests=2, max_new_tokens=3), obs=obs)
    t_before = time.perf_counter()
    sched.advance_to(1.0)
    for i in range(3):              # two batches: the third request waits
        sched.submit(np.arange(1, 5, dtype=np.int32), tier="economy",
                     arrival_s=0.1 * i)
    sched.run_until_idle()
    t_after = time.perf_counter()
    spans = obs.tracer.spans
    life = {"admit", "queue", "schedule", "release"}
    assert all(s.clock == "wall" and t_before <= s.t0_s <= s.t1_s <= t_after
               for s in spans if s.name in life)
    for rid in range(3):
        mine = {s.name: s for s in spans if s.request_id == rid}
        a, q, r = mine["admit"], mine["queue"], mine["release"]
        assert a.t0_s == q.t0_s <= q.t1_s <= r.t0_s
        assert q.parent_id == a.span_id and r.parent_id == a.span_id
        # the simulated clock rides along as attributes
        done = sched.completed[rid]
        assert a.attrs["sim_t0_s"] == pytest.approx(0.1 * rid)
        assert q.attrs["sim_t0_s"] == pytest.approx(0.1 * rid)
        assert q.attrs["queue_delay_s"] == pytest.approx(done.queue_delay_s)
        assert q.attrs["sim_t1_s"] - q.attrs["sim_t0_s"] == \
            pytest.approx(done.queue_delay_s)
        assert r.attrs["latency_s"] == pytest.approx(done.latency_s)
    # the host phases: every batch forms inside a step, routed inside it
    by_id = {s.span_id: s for s in spans}
    for name, parent in (("route", "sched.form_batch"),
                         ("sched.form_batch", "sched.step"),
                         ("sched.retire", "sched.step"),
                         ("schedule", "sched.form_batch")):
        kids = [s for s in spans if s.name == name]
        assert kids and all(by_id[s.parent_id].name == parent for s in kids)
    # serving_queue_delay_s observes the queue spans' own wall intervals
    h = obs.metrics.get("serving_queue_delay_s")
    waits = [s.t1_s - s.t0_s for s in spans if s.name == "queue"]
    assert h.total(tier="economy") == 3
    assert h.sum_value(tier="economy") == pytest.approx(sum(waits))
    assert obs.metrics.get("serving_batch_latency_s") is None


def test_admission_reject_reason_codes():
    obs = make_observability()
    q = RequestQueue(router=_StubRouter(_tiers3()), max_queue_depth=1,
                     obs=obs)
    p = np.arange(1, 4, dtype=np.int32)
    assert q.submit(p, tier="nope").reason_code == "unknown_tier"
    assert q.submit(p, tier="economy").admitted
    assert q.submit(p, tier="economy").reason_code == "queue_full"
    assert q.submit(p, tier="standard", n_samples=4,
                    budget=2).reason_code == "kv_budget"
    c = obs.metrics.get("serving_admission_total")
    assert c.value(outcome="rejected", reason="unknown_tier") == 1
    assert c.value(outcome="rejected", reason="queue_full") == 1
    assert c.value(outcome="rejected", reason="kv_budget") == 1
    assert c.value(outcome="admitted", reason="ok") == 1
    rejected = [s for s in obs.tracer.spans
                if s.name == "admit" and not s.attrs.get("admitted")]
    assert [s.attrs["reason"] for s in rejected] == \
        ["unknown_tier", "queue_full", "kv_budget"]


def test_serve_trace_records_carry_request_entries():
    from repro.qeil2 import TraceStore
    trace = TraceStore()
    sched = ContinuousBatchingScheduler(
        _StubBackend(), _StubRouter(_tiers3()),
        SchedulerConfig(max_batch_requests=4, max_new_tokens=3), trace=trace)
    sched.submit(np.arange(1, 5, dtype=np.int32), tier="standard")
    sched.run_until_idle()
    [rec] = trace.records("serve")
    assert rec["requests"][0]["tier"] == "standard"
    assert rec["requests"][0]["queue_delay_s"] >= 0.0


# ============================================== pinned: bit-parity obs on/off

CFG_KW = dict(name="t-obs", arch_type="dense", n_layers=2, d_model=64,
              n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=64)


def _run_real_stream(obs):
    import jax
    import jax.numpy as jnp
    from repro.models import ArchConfig, Model
    from repro.serving import ExecutionBackend

    model = Model(ArchConfig(**CFG_KW), dtype=jnp.float32)
    params = model.init(jax.random.key(0))
    backend = ExecutionBackend(model, params, obs=obs)
    sched = ContinuousBatchingScheduler(
        backend, _StubRouter(_tiers3()),
        SchedulerConfig(max_batch_requests=4, max_new_tokens=4, seed=3),
        obs=obs)
    ids = []
    for i in range(3):
        adm = sched.submit(np.arange(1, 4, dtype=np.int32) + i,
                           tier="economy", n_samples=2, temperature=0.8)
        ids.append(adm.request_id)
    done = sched.run_until_idle()
    return [(done[i].result.samples, done[i].result.logprobs) for i in ids]


def test_pinned_bit_parity_scheduler_obs_on_off():
    """The observability stack must be a pure observer: identical sampled
    tokens and logprobs with the full stack on vs off."""
    pytest.importorskip("jax")
    off = _run_real_stream(None)
    on_obs = make_observability()
    on = _run_real_stream(on_obs)
    assert len(on_obs.tracer) > 0                    # actually instrumented
    assert on_obs.metrics.get(
        "serving_tokens_out_total").value() > 0
    for (sa, la), (sb, lb) in zip(off, on):
        assert la == lb
        for a, b in zip(sa, sb):
            np.testing.assert_array_equal(a, b)


def test_pinned_bit_parity_engine_obs_on_off():
    pytest.importorskip("jax")
    import jax
    import jax.numpy as jnp
    from repro.models import ArchConfig, Model
    from repro.serving import ServingEngine

    model = Model(ArchConfig(**CFG_KW), dtype=jnp.float32)
    params = model.init(jax.random.key(0))
    prompt = np.arange(1, 6, dtype=np.int32)
    outs = []
    for obs in (None, make_observability()):
        engine = ServingEngine(model, params, max_new_tokens=4, obs=obs)
        [r] = engine.generate([prompt], n_samples=2,
                              rng=jax.random.key(11))
        outs.append((r.samples, r.logprobs))
    (sa, la), (sb, lb) = outs
    assert la == lb
    for a, b in zip(sa, sb):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("weights", ["bf16", "int8"])
def test_bit_parity_obs_on_off_at_reduced_chatglm3(weights):
    """The host-phase spans at the benchmark's reduced chatglm3-6b sizes
    (pooled paged KV, shared prefixes, sampled): identical tokens and
    log-probs with the tracer live, and every phase under its parent."""
    pytest.importorskip("jax")
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "chipbench"))
    from small_cells import small, small_arch

    from repro.models import Model
    from repro.serving import ExecutionBackend

    conf = small("chatglm3-6b-bf16.chat-shared").config
    model = Model(small_arch(conf), dtype=jnp.bfloat16)
    params = model.init(jax.random.key(0))
    if weights != "bf16":
        from repro.quant import quantize_model
        params = quantize_model(params, weights)
    rng = np.random.default_rng(5)
    shared = rng.integers(1, 500, 16).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(1, 500, 8)
                               .astype(np.int32)]) for _ in range(3)]

    def serve(obs):
        backend = ExecutionBackend(model, params, kv_blocks=64,
                                   kv_block_size=16, kv_pool=True, obs=obs)
        sched = ContinuousBatchingScheduler(
            backend, _StubRouter(_tiers3()),
            SchedulerConfig(max_batch_requests=2, max_new_tokens=5, seed=9),
            obs=obs)
        ids = [sched.submit(p, tier="economy", n_samples=2,
                            temperature=0.8).request_id for p in prompts]
        done = sched.run_until_idle()
        return [(done[i].result.samples, done[i].result.logprobs)
                for i in ids]

    off = serve(None)
    obs = make_observability()
    on = serve(obs)
    obs.tracer.close()
    for (sa, la), (sb, lb) in zip(off, on):
        assert la == lb
        for a, b in zip(sa, sb):
            np.testing.assert_array_equal(a, b)
    spans = obs.tracer.spans
    by_id = {s.span_id: s for s in spans}
    parents = {"pool.match": "prefill", "prefill.dispatch": "prefill",
               "prefill.fetch": "prefill", "decode.dispatch": "decode",
               "decode.fetch": "decode", "decode": "sched.step",
               "prefill": "sched.form_batch"}
    for name, parent in parents.items():
        kids = [s for s in spans if s.name == name]
        assert kids, name
        assert all(by_id[s.parent_id].name == parent for s in kids), name
    assert lifecycles_complete(spans, expect_requests=3)


# ================================================== compare.py + profile.py

def test_bench_compare_identity_and_regression():
    import benchmarks.compare as bc
    base = bc.run(verbose=False)
    assert base["self_check_ok"]
    art = {"acceptance_all": True, "throughput_ratio": 2.0,
           "scheduler": {"completed": 5}}
    assert bc.compare(art, dict(art), "serving_schedule") == []
    worse = {"acceptance_all": True, "throughput_ratio": 1.0,
             "scheduler": {"completed": 5}}
    [f] = bc.compare(art, worse, "serving_schedule")
    assert f["path"] == "throughput_ratio"


def test_profile_records_feed_the_fitter():
    pytest.importorskip("jax")
    from repro.launch.profile import run as profile_run
    from repro.qeil2.telemetry.fit import _eta_key

    res = profile_run(verbose=False, reps=1, kernels=["dequant_matmul"])
    assert res["n_records"] == 2                     # int8 + int4, 1 rep each
    keys = sorted(_eta_key(r) for r in res["records"])
    assert keys == ["dequant_matmul:int4", "dequant_matmul:int8"]
    for r in res["records"]:
        assert r["kind"] == "kernel"
        assert r["flops"] > 0 and r["bytes"] > 0
        assert r["measured_us"] > 0 and r["roofline_us"] > 0


def test_cascade_metrics_and_verify_spans():
    from repro.core.sampling import VerifierCascade

    obs = make_observability()
    casc = VerifierCascade(exact_verify=lambda s: bool(s[-1] % 2),
                           early_stop=True, obs=obs)
    samples = [np.array([1, 2, 3]), np.array([1, 2, 5]), np.array([2, 2, 2])]
    casc.verify(samples, [-1.0, -0.5, -2.0], request_id=7)
    reg = obs.metrics
    assert reg.get("cascade_candidates_total").value() == 3
    assert reg.get("cascade_exact_passed_total").value() >= 1
    spans = [s for s in obs.tracer.spans if s.name == "verify"]
    assert spans and all(s.request_id == 7 for s in spans)
