"""Request-span tracing for the serving pipeline.

A span is one named interval. Two ways make one:

* ``with tracer.span(name, **attrs):`` times the block on the host's
  ``time.perf_counter()`` (``clock="wall"``) and opens a profiler
  ``TraceAnnotation("repro/<name>")`` for the same interval, so the span
  sits on the profiler's clock beside the device planes. Open spans form a
  stack: a span's ``parent_id`` is the span open around it.
* ``tracer.emit(name, t0_s, t1_s)`` records an interval (or a point) whose
  times the caller took; it stays for point events.

Serving phases on the host (all wall clock)::

    sched.step                          ContinuousBatchingScheduler.step
    ├── sched.form_batch                _form_batch
    │   ├── route                       router.route_batch
    │   ├── schedule (point)            the batch is committed
    │   └── prefill                     ExecutionBackend.start_batch
    │       ├── pool.match              trie lookup, allocation, tables
    │       ├── prefill.dispatch        jitted/eager device calls
    │       └── prefill.fetch           host reads of device values
    ├── decode                          ExecutionBackend.decode_step
    │   ├── decode.dispatch             the decode step program
    │   └── decode.fetch                token + log-prob reads
    └── sched.retire                    _retire, finalize included

A live tracer also hooks Python's collector and JAX's compiler: a ``gc``
span (generation, collected) and a ``compile`` span (the function name and
the open spans' shape attributes) land under whatever span is open when
they happen. `Tracer.close` removes both hooks; they also go when the
tracer is garbage-collected.

Request lifecycle (``request_id`` spans parent under the request's
``admit`` root; batch-level spans join through ``batch_id``)::

    admit ──► queue ──► [batch: schedule ──► prefill ──► decode*]
                   └──────────► verify/early_stop? ──► release

``admit`` is taken at submit, ``queue`` runs from submit to the entry of
the ``prefill`` span of the request's batch. The scheduler's simulated
clock, on which SLA caps and deadlines are defined, rides along as
attributes (``sim_t0_s``, ``sim_t1_s``, ``queue_delay_s``).
`reconstruct_lifecycles` rebuilds every admitted request's admit→release
chain and checks it is complete and time-ordered.

Spans are JSONL-ready dicts (`Span.as_record`) with ``kind: "span"`` —
`TraceStore` validates and persists them next to kernel/energy/serve
records.

`NullTracer` is the zero-cost default: ``enabled`` is False, ``emit`` is a
no-op and ``span`` returns one shared no-op context that reads no clock and
opens no annotation. Tracing is a pure observation — tracers never touch
the rng stream; the obs on/off bit-parity tests pin that.
"""
from __future__ import annotations

import gc
import json
import time
import weakref
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Union

#: canonical span names in lifecycle order (docs + lifecycle checker).
#: preempt/resume/cancel are the robustness detours: a preempted request
#: re-queues (original submit kept) and later emits a resume point span
#: when its history re-enters service; cancel ends a request without a
#: release (deadline miss, load shed, retry budget exhausted).
LIFECYCLE = ("admit", "queue", "schedule", "prefill", "decode",
             "verify", "early_stop", "preempt", "resume", "cancel",
             "release")

#: profiler name prefix of every span (``repro/decode.fetch``)
PROFILER_PREFIX = "repro/"

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclass
class Span:
    span_id: int
    name: str
    t0_s: float
    t1_s: float
    clock: str = "sim"                 # "sim" | "wall"
    parent_id: Optional[int] = None
    request_id: Optional[int] = None
    batch_id: Optional[int] = None
    sample: Optional[int] = None       # sample index within the request
    attrs: Dict[str, Any] = field(default_factory=dict)

    def as_record(self) -> Dict[str, Any]:
        rec: Dict[str, Any] = {"kind": "span", "span_id": self.span_id,
                               "name": self.name, "t0_s": self.t0_s,
                               "t1_s": self.t1_s, "clock": self.clock}
        for k in ("parent_id", "request_id", "batch_id", "sample"):
            v = getattr(self, k)
            if v is not None:
                rec[k] = v
        if self.attrs:
            rec["attrs"] = dict(self.attrs)
        return rec


class _NullSpan:
    """The disabled span: enters, exits and takes attributes, doing
    nothing."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass


NULL_SPAN = _NullSpan()


class _LiveSpan:
    """An open wall-clock span: times its block, holds its profiler
    annotation, and records itself on exit."""

    __slots__ = ("_tr", "name", "attrs", "span_id", "parent_id", "batch_id",
                 "t0", "_ann")

    def __init__(self, tracer: "Tracer", name: str, ann, attrs):
        self._tr = tracer
        self.name = name
        self.attrs = attrs
        self._ann = ann

    def __enter__(self):
        tr = self._tr
        self.span_id = tr._next
        tr._next += 1
        self.parent_id = tr._stack[-1].span_id if tr._stack else None
        self.batch_id = tr.batch_context
        tr._stack.append(self)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        tr._opened[self.name] = self.t0
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        tr = self._tr
        tr._stack.remove(self)
        tr._record(Span(self.span_id, self.name, self.t0, t1, clock="wall",
                        parent_id=self.parent_id, batch_id=self.batch_id,
                        attrs=self.attrs))
        return False

    def set(self, **attrs) -> None:
        """Add attributes known only inside the span (sizes, counts)."""
        self.attrs.update(attrs)


def _weak_callback(method):
    """A callback that calls ``method`` while its object lives, so a hook
    never keeps a tracer alive."""
    ref = weakref.WeakMethod(method)

    def callback(*args, **kwargs):
        m = ref()
        if m is not None:
            m(*args, **kwargs)
    return callback


def _unhook(gc_cb, compile_cb) -> None:
    if gc_cb in gc.callbacks:
        gc.callbacks.remove(gc_cb)
    from jax import monitoring
    try:
        monitoring.unregister_event_duration_listener(compile_cb)
    except AssertionError:
        pass                           # listeners cleared elsewhere


class Tracer:
    """Collects spans; optionally mirrors them into a `TraceStore`.

    ``span`` times a block on the wall clock and ``emit`` records an
    interval with the caller's timestamps. ``batch_context`` is scratch
    the scheduler sets around backend calls so backend spans pick up the
    batch's id without widening the duck-typed backend signature.
    """

    enabled = True

    def __init__(self, store=None):
        self.spans: List[Span] = []
        self.store = store             # optional TraceStore mirror
        self.batch_context: Optional[int] = None
        self._roots: Dict[int, int] = {}   # request_id -> admit span_id
        self._next = 0
        self._stack: List[_LiveSpan] = []
        self._opened: Dict[str, float] = {}    # name -> latest entry time
        import jax                     # a live tracer only: obs stays light
        self._annotation = jax.profiler.TraceAnnotation
        self._step_annotation = jax.profiler.StepTraceAnnotation
        self._gc_open: Optional[tuple] = None
        gc_cb = _weak_callback(self._on_gc)
        gc.callbacks.append(gc_cb)
        compile_cb = _weak_callback(self._on_duration)
        jax.monitoring.register_event_duration_secs_listener(compile_cb)
        self._unhook = weakref.finalize(self, _unhook, gc_cb, compile_cb)
        self._unhook.atexit = False

    # ---------------------------------------------------------------- spans
    def span(self, name: str, step_num: Optional[int] = None, **attrs):
        """Context manager timing its block as a wall-clock span named
        ``name``, under a profiler annotation ``repro/<name>`` (a step
        annotation when ``step_num`` is given, which is then also an
        attribute)."""
        label = PROFILER_PREFIX + name
        if step_num is None:
            ann = self._annotation(label)
        else:
            ann = self._step_annotation(label, step_num=step_num)
            attrs["step_num"] = step_num
        return _LiveSpan(self, name, ann, attrs)

    def opened_at(self, name: str) -> Optional[float]:
        """Wall entry time of the latest span opened under ``name``."""
        return self._opened.get(name)

    def emit(self, name: str, t0_s: float, t1_s: Optional[float] = None,
             *, clock: str = "sim", request_id: Optional[int] = None,
             batch_id: Optional[int] = None, sample: Optional[int] = None,
             parent_id: Optional[int] = None, **attrs) -> int:
        """Record one span; returns its id. ``t1_s`` defaults to ``t0_s``
        (a point event). An ``admit`` span becomes its request's root;
        later spans with that ``request_id`` parent under it, others under
        the innermost open span."""
        sid = self._next
        self._next += 1
        if batch_id is None:
            batch_id = self.batch_context
        if parent_id is None:
            if request_id is not None:
                parent_id = self._roots.get(request_id)
            elif self._stack:
                parent_id = self._stack[-1].span_id
        span = Span(sid, name, float(t0_s),
                    float(t1_s if t1_s is not None else t0_s),
                    clock=clock, parent_id=parent_id, request_id=request_id,
                    batch_id=batch_id, sample=sample, attrs=attrs)
        if name == "admit" and request_id is not None:
            self._roots[request_id] = sid
        self._record(span)
        return sid

    def _record(self, span: Span) -> None:
        self.spans.append(span)
        if self.store is not None:
            self.store.ingest(span.as_record())

    def _open_attrs(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for s in self._stack:
            out.update(s.attrs)
        return out

    # ---------------------------------------------------------------- hooks
    def _on_gc(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            ann = self._annotation(PROFILER_PREFIX + "gc")
            ann.__enter__()
            self._gc_open = (time.perf_counter(), ann)
        elif self._gc_open is not None:
            t0, ann = self._gc_open
            self._gc_open = None
            t1 = time.perf_counter()
            ann.__exit__(None, None, None)
            self.emit("gc", t0, t1, clock="wall",
                      generation=info.get("generation"),
                      collected=info.get("collected"))

    def _on_duration(self, event: str, secs: float, **kwargs) -> None:
        if event != COMPILE_EVENT:
            return
        t1 = time.perf_counter()
        self.emit("compile", t1 - secs, t1, clock="wall",
                  fun_name=kwargs.get("fun_name"), **self._open_attrs())

    def close(self) -> None:
        """Remove the collector and compiler hooks (idempotent)."""
        self._unhook()

    # -------------------------------------------------------------- output
    def records(self) -> List[Dict[str, Any]]:
        return [s.as_record() for s in self.spans]

    def save(self, path: str) -> str:
        """Write every span as one JSON line (`TraceStore.load`-compatible)."""
        with open(path, "w") as f:
            for s in self.spans:
                f.write(json.dumps(s.as_record()) + "\n")
        return path

    def __len__(self) -> int:
        return len(self.spans)


class NullTracer:
    """Disabled tracer: ``emit`` no-ops, ``span`` returns the shared no-op
    context; hot paths guard on ``enabled``."""

    enabled = False

    def __init__(self):
        self.spans: List[Span] = []
        self.batch_context: Optional[int] = None

    def span(self, name: str, step_num: Optional[int] = None, **attrs):
        return NULL_SPAN

    def opened_at(self, name: str) -> Optional[float]:
        return None

    def emit(self, *a, **k) -> int:
        return -1

    def records(self) -> List[Dict[str, Any]]:
        return []

    def save(self, path: str) -> str:
        raise RuntimeError("NullTracer has no spans to save; construct a "
                           "Tracer (repro.obs.make_observability)")

    def __len__(self) -> int:
        return 0


# ------------------------------------------------------- lifecycle checking

def _as_dicts(spans: Iterable[Union[Span, Dict[str, Any]]]
              ) -> List[Dict[str, Any]]:
    return [s.as_record() if isinstance(s, Span) else s for s in spans]


def reconstruct_lifecycles(spans: Iterable[Union[Span, Dict[str, Any]]]
                           ) -> Dict[int, Dict[str, Any]]:
    """Rebuild every admitted request's admit→release chain from a span set.

    Returns ``{request_id: {"complete": bool, "missing": [...],
    "batch_id": ..., "queue_delay_s": ..., "latency_s": ...}}``. A chain is
    complete when the request has admit, queue and release spans, its queue
    span names a batch that emitted schedule + prefill + >=1 decode span,
    and the wall-clock times are ordered (admit <= queue start <= queue end
    <= release). Rejected submissions (admit spans with no ``request_id``)
    are not lifecycles and are ignored.
    """
    recs = _as_dicts(spans)
    by_req: Dict[int, Dict[str, List[dict]]] = {}
    by_batch: Dict[int, Dict[str, List[dict]]] = {}
    for r in recs:
        if r.get("kind", "span") != "span":
            continue
        rid, bid = r.get("request_id"), r.get("batch_id")
        if rid is not None:
            by_req.setdefault(rid, {}).setdefault(r["name"], []).append(r)
        elif bid is not None:
            by_batch.setdefault(bid, {}).setdefault(r["name"], []).append(r)

    out: Dict[int, Dict[str, Any]] = {}
    for rid, named in sorted(by_req.items()):
        if "admit" not in named:
            continue
        missing = [n for n in ("admit", "queue", "release") if n not in named]
        admit = named["admit"][0]
        queue = named.get("queue", [{}])[0]
        release = named.get("release", [{}])[0]
        bid = queue.get("batch_id", release.get("batch_id"))
        batch = by_batch.get(bid, {})
        for n in ("schedule", "prefill", "decode"):
            if n not in batch and n not in named:
                missing.append(n)
        ordered = not missing and (
            admit["t0_s"] <= queue["t0_s"] <= queue["t1_s"]
            <= release["t1_s"])
        out[rid] = {
            "complete": not missing and ordered,
            "missing": missing,
            "batch_id": bid,
            "queue_delay_s": (queue["t1_s"] - queue["t0_s"]
                              if "queue" in named else None),
            "latency_s": (release["t1_s"] - admit["t0_s"]
                          if "release" in named else None),
        }
    return out


def lifecycles_complete(spans: Iterable[Union[Span, Dict[str, Any]]],
                        expect_requests: Optional[int] = None) -> bool:
    """True when every reconstructed lifecycle is complete (and, when
    ``expect_requests`` is given, exactly that many requests appear)."""
    lifecycles = reconstruct_lifecycles(spans)
    if expect_requests is not None and len(lifecycles) != expect_requests:
        return False
    return bool(lifecycles) and all(v["complete"]
                                    for v in lifecycles.values())
