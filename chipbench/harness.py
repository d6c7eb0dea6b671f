"""One run of one cell: set-up, warm-up, the measured window, the output
check, and the metrics.

Everything is timed on the harness's clock (`chipbench.stack.clock`);
nothing reads the scheduler's simulated clock.
"""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from chipbench import check as check_mod
from chipbench import registry
from chipbench.flops import Sizes
from chipbench.stack import Recorder, build, clock, listen_compiles
from chipbench.traffic import Mix, Request, seed_words

DRAIN_S = 60.0          # after the window: time to finish what was due
TRACE_S = 5.0           # traced span: the window's last seconds


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class ReqRec:
    req: Request
    rid: int
    due: float                      # harness clock, window-relative
    submitted: float
    admitted: bool = True
    started: Optional[float] = None     # begin of its start_batch call
    first_token: Optional[float] = None  # end of its start_batch call
    done: Optional[float] = None
    served: Optional[check_mod.Served] = None


@dataclass
class Run:
    """What one run recorded; the metric readers read this."""
    seconds: float
    setup_s: float
    sizes: Sizes
    peaks: Any
    reqs: List[ReqRec] = field(default_factory=list)
    calls: List[Any] = field(default_factory=list)
    compile_times: List[float] = field(default_factory=list)
    trace: Optional[Dict[str, Any]] = None   # reduced device trace
    trace_span: Optional[tuple] = None       # (begin, end), window clock


# ---------------------------------------------------------------- warm-up

def warm_up(stack, mix: Mix, conf: dict, seed: int) -> Dict[str, Any]:
    """Compile and load every program the window can call, and fill the
    pool as the window finds it: each shared prefix resident.

    Every shape comes from the cell's own sizes: for each prompt length
    and batch size up to ``max_batch_requests``, one
    ``start_batch`` (prefill over the pooled prefixes), one
    ``decode_step`` and a release, through the backend's own calls. Then one
    request of the warm-up stream runs through the scheduler, which warms
    its own path. The prefixes are filled last, least popular first, so the
    pool's LRU order at the window's start is that of steady state."""
    import jax

    be = stack.backend
    p = mix.params
    rng = np.random.default_rng(seed_words(seed) + [4])
    key = jax.random.key(seed_words(seed)[0] & 0x7FFFFFFF)
    max_batch = conf["scheduler"]["max_batch_requests"]
    n_pre = mix.prefixes.shape[0]
    allowed = {}
    for plen, suffix in zip(mix.prompt_lengths, mix.suffix_lengths):
        allowed[plen] = [n for n in range(1, max_batch + 1)
                         if stack.router.route_batch(
                             [p["tier"]] * n, samples=p["n_samples"],
                             prompt_tokens=plen,
                             decode_tokens=p["max_new_tokens"]).meets_caps]

    def batch(prefix_ids, suffix):
        prompts = [mix.prompt(k, suffix, rng) for k in prefix_ids]
        h = be.start_batch(prompts, p["n_samples"], p["max_new_tokens"],
                           p["temperature"], key)
        be.decode_step(h)
        be.release(h)

    order = list(range(n_pre))[::-1]          # least popular first
    for k in order:                            # cold prefixes, one a batch
        batch([k], mix.suffix_lengths[0])
    used = 0                                   # every prefix stays warm
    for suffix in mix.suffix_lengths:
        for n in range(1, max_batch + 1):
            batch([(used + j) % n_pre for j in range(n)], suffix)
            used += n
    first = mix.requests("warmup", 1)[0]
    sched = stack.sched
    sched.submit(first.prompt, first.tier, n_samples=first.n_samples,
                 max_new_tokens=first.max_new,
                 temperature=first.temperature)
    sched.run_until_idle()
    sched.completed.clear()
    sched.backend.served.clear()
    for k in order:                            # refresh LRU: popular last
        batch([k], mix.suffix_lengths[0])
    return allowed


# ----------------------------------------------------------------- window

def _submit(sched, r: Request, due: float, t0: float) -> ReqRec:
    now = clock() - t0
    adm = sched.submit(r.prompt, r.tier, n_samples=r.n_samples,
                       max_new_tokens=r.max_new, temperature=r.temperature)
    return ReqRec(r, adm.request_id if adm.admitted else -1, due, now,
                  admitted=adm.admitted)


def _collect(sched, by_id: Dict[int, ReqRec], t0: float) -> List[ReqRec]:
    done = []
    for rid in list(sched.completed):
        c = sched.completed.pop(rid)
        lps, seqs = sched.backend.served.pop(id(c.result))
        rec = by_id.get(rid)
        if rec is None:
            continue
        rec.done = clock() - t0
        rec.served = check_mod.Served(
            rec.req, [np.asarray(s) for s in c.result.samples], lps, seqs)
        done.append(rec)
    return done


class Tracer:
    """Starts the profiler at the first step boundary of the window's last
    ``TRACE_S`` seconds, and stops it when the window has closed: writing
    the trace out takes seconds, which must not stall the window."""

    def __init__(self, seconds: float, on: bool):
        self.on = on
        self.begin = max(0.0, seconds - TRACE_S)
        self.dir = None
        self.span = None
        self._t = None

    def boundary(self, now: float) -> None:
        import jax
        if self.on and self.dir is None and now >= self.begin:
            self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
            jax.profiler.start_trace(self.dir)
            self._t = now

    def close(self, now: float) -> None:
        import jax
        if self.dir is not None and self.span is None:
            jax.profiler.stop_trace()
            self.span = (self._t, now)


def open_loop(sched, reqs: List[Request], seconds: float,
              tracer: Tracer) -> Tuple[List[ReqRec], float]:
    """Submit each request once it is due, step while there is work; then
    drain. Returns the records and the window's start on the clock."""
    t0 = clock()
    recs: List[ReqRec] = []
    by_id: Dict[int, ReqRec] = {}
    i = 0
    while True:
        now = clock() - t0
        if now >= seconds:
            break
        tracer.boundary(now)
        while i < len(reqs) and reqs[i].due <= now:
            rec = _submit(sched, reqs[i], reqs[i].due, t0)
            recs.append(rec)
            by_id[rec.rid] = rec
            i += 1
        if sched.queue.pending or sched.inflight:
            sched.step()
        else:
            nxt = reqs[i].due if i < len(reqs) else seconds
            time.sleep(max(0.0, min(nxt, seconds) - (clock() - t0)))
        _collect(sched, by_id, t0)
    tracer.close(clock() - t0)
    while i < len(reqs):                 # due inside the window, sent late
        rec = _submit(sched, reqs[i], reqs[i].due, t0)
        recs.append(rec)
        by_id[rec.rid] = rec
        i += 1
    _drain(sched, by_id, t0, seconds)
    return recs, t0


def closed_loop(sched, reqs, outstanding: int, seconds: float,
                tracer: Tracer) -> Tuple[List[ReqRec], float]:
    """``outstanding`` clients; each sends its next request (the next of
    the iterator ``reqs``) when its last one completes; then drain."""
    t0 = clock()
    recs: List[ReqRec] = []
    by_id: Dict[int, ReqRec] = {}

    def send():
        now = clock() - t0
        rec = _submit(sched, next(reqs), now, t0)
        recs.append(rec)
        by_id[rec.rid] = rec

    for _ in range(outstanding):
        send()
    while True:
        now = clock() - t0
        if now >= seconds:
            break
        tracer.boundary(now)
        sched.step()
        for _ in _collect(sched, by_id, t0):
            if clock() - t0 < seconds:
                send()
    tracer.close(clock() - t0)
    _drain(sched, by_id, t0, seconds)
    return recs, t0


def _drain(sched, by_id, t0, seconds) -> None:
    while (sched.queue.pending or sched.inflight) and \
            clock() - t0 < seconds + DRAIN_S:
        sched.step()
        _collect(sched, by_id, t0)


# ------------------------------------------------------------------- run

def _device_info(jax) -> Dict[str, Any]:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def run_cell(cell: registry.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, peaks=None, controls=()) -> Dict[str, Any]:
    """One run; returns the result object (the last line's). ``controls``
    also reads the gaps of forwards in those weight formats
    (`chipbench.control`)."""
    import jax

    from chipbench.peaks import peaks_for

    conf, mix_params = cell.config, cell.traffic
    device = _device_info(jax)
    peaks = peaks if peaks is not None else peaks_for(device["kind"])
    rec = Recorder()
    listen_compiles(rec)
    mix = Mix(mix_params, conf["padded_vocab_size"], seed)
    stack = build(conf, mix, seed, rec)
    log(f"[setup] weights, router and backend built at "
        f"{clock() - t_start:.3f}s")
    rec.enabled = False
    allowed = warm_up(stack, mix, conf, seed)
    rec.enabled = True
    log(f"[setup] batch sizes the router's caps allow per prompt length: "
        f"{allowed}")
    if mix_params["loop"] == "open":
        reqs = mix.schedule(seconds)
    else:
        reqs = mix.blocks("window")
    gc.collect()
    rec.reset()
    setup_s = clock() - t_start
    tracer = Tracer(seconds, trace)
    if mix_params["loop"] == "open":
        recs, t0 = open_loop(stack.sched, reqs, seconds, tracer)
    else:
        recs, t0 = closed_loop(stack.sched, reqs, mix_params["outstanding"],
                               seconds, tracer)
    rec.enabled = False
    d = jax.devices()[0]
    stats = d.memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    run = Run(seconds=seconds, setup_s=setup_s, sizes=Sizes.of(conf),
              peaks=peaks, reqs=recs)
    run.calls = [_shift(c, t0) for c in rec.calls]
    run.compile_times = [t - t0 for t in rec.compile_times]
    _attach_calls(run)
    late = [r.submitted - r.due for r in recs]
    steps = sorted(c.end for c in run.calls
                   if c.kind in ("start_batch", "decode_step")
                   and 0.0 <= c.end <= seconds)
    log(f"[window] {len(steps)} prefill and decode calls ended in the "
        f"window; longest time between two: "
        f"{max(np.diff(steps), default=0.0):.6f}s")
    log(f"[window] {len(recs)} requests sent, "
        f"{sum(r.admitted for r in recs)} admitted, "
        f"{sum(r.done is not None for r in recs)} completed; generator "
        f"lateness max {max(late, default=0.0):.6f}s mean "
        f"{(sum(late) / len(late)) if late else 0.0:.6f}s; "
        f"peak_bytes_in_use {device['memory_peak_bytes']} of "
        f"{stats.get('bytes_limit')}")
    if tracer.dir is not None:
        from chipbench import tracefile
        try:
            run.trace = tracefile.reduce(tracefile.extract(tracer.dir),
                                         tracer.span)
            run.trace_span = tracer.span
        finally:
            shutil.rmtree(tracer.dir, ignore_errors=True)
    # free the program's state before the reference runs on the device
    served = [r.served for r in recs if r.served is not None]
    del stack
    gc.collect()
    log(f"[check] {sum(a.nbytes for a in jax.live_arrays())} bytes of "
        f"arrays live before the reference")
    t_check = clock()
    verdict = check_mod.check(conf, seed, served, controls=controls)
    log(f"[check] reference took {clock() - t_check:.3f}s")
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = registry.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": verdict["correct"],
           "attempted": len(recs),
           "failed": sum(1 for r in recs
                         if not r.admitted or r.done is None),
           "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        out["device"]["busy_s"] = run.trace["busy_s"]
        out["device"]["window_s"] = run.trace["window_s"]
        out["breakdown"] = run.trace["breakdown"]
    out.update({k: v for k, v in verdict.items()
                if k not in ("correct", "compared")})
    out["compared"] = verdict["compared"]
    return out


def _shift(call, t0):
    call.begin -= t0
    call.end -= t0
    return call


def _attach_calls(run: Run) -> None:
    """Each request's start (begin of its start_batch) and first token (end
    of it)."""
    by_prompt = {id(r.req.prompt): r for r in run.reqs}
    for c in run.calls:
        if c.kind != "start_batch":
            continue
        for pid in c.prompt_ids:
            r = by_prompt.get(pid)
            if r is not None and r.started is None:
                r.started, r.first_token = c.begin, c.end
