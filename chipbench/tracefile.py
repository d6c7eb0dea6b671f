"""Reduction of a profiler trace to device busy time, idle gaps, module
and kernel time.

`extract` reads the ``.xplane.pb`` a `jax.profiler` session wrote into a
plain dict (kept small: the device planes' op and module events, and the
harness's own host annotations); `reduce` works on that dict alone, so a
recorded or synthetic dict checks it without a chip.

The dict: ``{"devices": [{"name", "ops": [[name, start_ns, dur_ns,
scope], ...], "modules": [[name, start_ns, dur_ns], ...]}], "host":
[[name, start_ns, dur_ns], ...]}``. ``scope`` is the op's source scope
(``tf_op``/``long_name``/``hlo_op`` text), where kernels' named scopes
appear.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "chipbench/"
SCOPE_STATS = ("tf_op", "long_name", "hlo_op", "name")
TOP = 10
# ops that hold other ops (a scanned layer stack is one ``while``): in the
# busy union, but not counted as an op of their own
CONTAINERS = ("while", "conditional", "call")
OP = re.compile(r"%?([\w.-]+)(?: = (\w+\[[\d,]*\]))?")


def op_name(name: str) -> str:
    """An op's instruction name and result shape, without the layout and
    operands of its HLO text: ``fusion.3 = bf16[32,4096]``."""
    m = OP.match(name)
    if m is None:
        return name
    return f"{m[1]} = {m[2]}" if m[2] else m[1]


def _stats(event) -> Dict[str, Any]:
    out = {}
    for k, v in event.stats:
        out[k] = v
    return out


def extract(trace_dir: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out: Dict[str, Any] = {"devices": [], "host": []}
    for path in paths:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            if plane.name.startswith("/device:TPU:") and \
                    "Core" not in plane.name[len("/device:TPU:"):]:
                dev = {"name": plane.name, "ops": [], "modules": []}
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        for e in line.events:
                            st = _stats(e)
                            scope = " ".join(str(st[k]) for k in SCOPE_STATS
                                             if k in st)
                            dev["ops"].append([e.name, e.start_ns,
                                               e.duration_ns, scope])
                    elif line.name == MODULES_LINE:
                        dev["modules"].extend([e.name, e.start_ns,
                                               e.duration_ns]
                                              for e in line.events)
                out["devices"].append(dev)
            elif plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    out["host"].extend(
                        [e.name, e.start_ns, e.duration_ns]
                        for e in line.events
                        if e.name.startswith(HOST_PREFIX))
    return out


def union_ns(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    """Merged [start, end] intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _host_at(host, t: float) -> str:
    """The innermost harness annotation open at ``t``, or ``host idle``."""
    best: Optional[list] = None
    for name, s, d in host:
        if s <= t < s + d and (best is None or d < best[2]):
            best = [name, s, d]
    return best[0][len(HOST_PREFIX):] if best else "host idle"


def reduce(t: Dict[str, Any], span: Optional[Tuple[float, float]] = None
           ) -> Dict[str, Any]:
    """busy_s (union of op intervals, averaged over devices), window_s (the
    traced span: the host clock's ``span`` if given, else first op to last),
    per-op and per-module device seconds, and ``breakdown``: the ops that
    took most time and the longest idle gaps named by what the host was
    doing."""
    devices = [d for d in t["devices"] if d["ops"]]
    if not devices:
        raise ValueError("the trace holds no device op")
    busy, ops, modules, gaps = [], {}, {}, []
    scopes: Dict[str, float] = {}
    for d in devices:
        iv = [(s, s + dur) for _, s, dur, _ in d["ops"]]
        merged = union_ns(iv)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for name, s, dur, scope in d["ops"]:
            name = op_name(name)
            if name.split(".")[0] in CONTAINERS:
                continue
            ops[name] = ops.get(name, 0.0) + dur * 1e-9
            scopes[scope] = scopes.get(scope, 0.0) + dur * 1e-9
        for name, s, dur in d["modules"]:
            modules[name] = modules.get(name, 0.0) + dur * 1e-9
        for (s0, e0), (s1, _) in zip(merged, merged[1:]):
            gaps.append((s1 - e0, e0))
    first = min(d["ops"][0][1] for d in devices)
    last = max(max(s + dur for _, s, dur, _ in d["ops"]) for d in devices)
    window_s = (span[1] - span[0]) if span else (last - first) * 1e-9
    gaps.sort(reverse=True)
    return {
        "busy_s": sum(busy) / len(busy),
        "window_s": window_s,
        "op_s": ops,
        "scope_s": scopes,
        "module_s": modules,
        "breakdown": {
            "device_ops": sorted(([n, v] for n, v in ops.items()),
                                 key=lambda x: -x[1])[:TOP],
            "idle_gaps": [[_host_at(t["host"], at), g * 1e-9]
                          for g, at in gaps[:TOP]],
        },
    }


def seconds_matching(table: Dict[str, float], needle: str) -> float:
    return sum(v for k, v in table.items() if needle in k)
