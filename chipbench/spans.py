"""The program's own spans (`repro.obs.Tracer`) read beside the harness's
calls: the host gap between device launches, the wall queue wait, the
collector's pauses, and device-idle gaps named by the host phase open
across them.

A span record is the dict `repro.obs.Span.as_record` gives: ``name``,
``t0_s``/``t1_s`` on ``time.perf_counter()`` (the harness clock) and
``clock``. `window_spans` keeps the wall ones and shifts them to the
window's clock; every reader here takes a run with ``seconds`` and
``spans`` (a list of such records, or None) and returns None where the
program recorded no host phase (a program without `Tracer.span`), so the
same reader runs against a program that lacks them.

Profiler side: every span is also a host event ``repro/<name>`` on the
profiler's clock; `program_events` reads them from a trace directory and
`named_gaps` names each device-idle gap ``<harness name>/<program span>``,
the program span being the innermost one open at the gap's midpoint (at
its start the host is still in the fetch that waited).
"""
from __future__ import annotations

import glob
import os
from typing import Any, Dict, List, Optional, Sequence

from chipbench import tracefile
from chipbench.stats import percentile

PREFIX = "repro/"
STEP = "sched.step"


def window_spans(records: Sequence[Dict[str, Any]], t0: float
                 ) -> List[Dict[str, Any]]:
    """The wall-clock spans of ``records``, shifted so the window starts
    at 0."""
    return [dict(r, t0_s=r["t0_s"] - t0, t1_s=r["t1_s"] - t0)
            for r in records if r.get("clock") == "wall"]


def _spans(run) -> Optional[List[Dict[str, Any]]]:
    spans = getattr(run, "spans", None)
    if not spans or not any(s["name"] == STEP for s in spans):
        return None
    return spans


def host_gaps(spans, begin: float, end: float) -> List[float]:
    """For each ``*.fetch`` span that ends in ``[begin, end]``, the time
    from its end to the end of the next ``*.dispatch`` span, where that
    ends by ``end``: the host time with nothing queued on the device."""
    fetch = sorted(s["t1_s"] for s in spans if s["name"].endswith(".fetch"))
    disp = sorted(s["t1_s"] for s in spans
                  if s["name"].endswith(".dispatch"))
    gaps, j = [], 0
    for f in fetch:
        while j < len(disp) and disp[j] <= f:
            j += 1
        if j == len(disp):
            break
        if begin <= f <= end and disp[j] <= end:
            gaps.append(disp[j] - f)
    return gaps


def host_gap_ms(run) -> Optional[float]:
    """Mean host gap between consecutive device launches in the window,
    ms."""
    spans = _spans(run)
    gaps = host_gaps(spans, 0.0, run.seconds) if spans else []
    return 1e3 * sum(gaps) / len(gaps) if gaps else None


def queue_span_p(run, q: float) -> Optional[float]:
    """``q``-th percentile of the wall ``queue`` spans of the requests
    submitted in the window, ms; a request not started by the window's end
    enters with its age then."""
    spans = _spans(run)
    if spans is None:
        return None
    first = {}
    for s in spans:
        rid = s.get("request_id")
        if s["name"] == "queue" and rid is not None and \
                (rid not in first or s["t1_s"] < first[rid]):
            first[rid] = s["t1_s"]
    vals = []
    for s in spans:
        rid = s.get("request_id")
        if s["name"] != "admit" or rid is None or \
                not 0.0 <= s["t0_s"] < run.seconds:
            continue
        start = first.get(rid)
        if start is None or start > run.seconds:
            start = run.seconds
        vals.append(start - s["t0_s"])
    return 1e3 * percentile(vals, q) if vals else None


def gc_pause_ms(run) -> Optional[float]:
    """Total duration of the collector's pauses that end in the window,
    ms."""
    spans = _spans(run)
    if spans is None:
        return None
    return 1e3 * sum(s["t1_s"] - s["t0_s"] for s in spans
                     if s["name"] == "gc"
                     and 0.0 <= s["t1_s"] <= run.seconds)


# ------------------------------------------------------------- profiler

def program_events(trace_dir: str) -> List[list]:
    """The ``repro/`` host events of a profiler session: ``[[name,
    start_ns, dur_ns], ...]``."""
    from jax.profiler import ProfileData
    out: List[list] = []
    for path in glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                       "*.xplane.pb")):
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    out.extend([e.name, e.start_ns, e.duration_ns]
                               for e in line.events
                               if e.name.startswith(PREFIX))
    return out


def innermost(events, t: float) -> Optional[str]:
    """The shortest ``repro/`` event open at ``t``, without its prefix."""
    best = None
    for name, s, d in events:
        if s <= t < s + d and (best is None or d < best[2]):
            best = (name, s, d)
    return best[0][len(PREFIX):] if best else None


def named_gaps(t: Dict[str, Any], events, top: int = tracefile.TOP
               ) -> List[list]:
    """The ``top`` longest device-idle gaps of an extracted trace ``t``
    (`tracefile.extract`), longest first, as ``[name, seconds, start_ns]``:
    the harness annotation open at the gap's start, as `tracefile.reduce`
    names it, then ``/`` and the innermost program span open at its
    midpoint, where one is."""
    gaps = []
    for d in t["devices"]:
        merged = tracefile.union_ns([(s, s + dur)
                                     for _, s, dur, _ in d["ops"]])
        gaps.extend((s1 - e0, e0) for (_, e0), (s1, _) in
                    zip(merged, merged[1:]))
    gaps.sort(reverse=True)
    out = []
    for g, at in gaps[:top]:
        name = tracefile._host_at(t["host"], at)
        phase = innermost(events, at + g / 2)
        out.append([f"{name}/{phase}" if phase else name, g * 1e-9, at])
    return out
