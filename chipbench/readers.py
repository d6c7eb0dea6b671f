"""Arithmetic shared by the metric readers under ``metrics/``.

Times are on the harness clock, relative to the window's start; the window
is ``[0, run.seconds]``.
"""
from __future__ import annotations

from typing import List, Optional

from chipbench import flops
from chipbench.stats import percentile, weighted_percentile


def in_window(run, t: Optional[float]) -> bool:
    return t is not None and 0.0 <= t <= run.seconds


def due_in_window(run) -> List:
    return [r for r in run.reqs if 0.0 <= r.due < run.seconds]


def censored(run, t: Optional[float], due: float) -> float:
    """``t - due``, or the request's age at the window's end when ``t`` did
    not come inside the window: a stall cannot hide."""
    return (t if in_window(run, t) else run.seconds) - due


def ttft_p(run, q: float) -> Optional[float]:
    vals = [censored(run, r.first_token, r.due) for r in due_in_window(run)]
    return None if not vals else 1e3 * percentile(vals, q)


def queue_wait_p(run, q: float) -> Optional[float]:
    vals = [censored(run, r.started, r.due) for r in due_in_window(run)]
    return None if not vals else 1e3 * percentile(vals, q)


def token_times(run):
    """Per batch: the end times of the calls that produced its tokens, and
    its sequence count."""
    batches = {}
    for c in run.calls:
        if c.kind in ("start_batch", "decode_step"):
            b = batches.setdefault(c.batch, [c.n_seqs, []])
            b[1].append(c.end)
    return batches


def tbt_p(run, q: float) -> Optional[float]:
    pairs = []
    for n, times in token_times(run).values():
        for a, b in zip(times, times[1:]):
            if in_window(run, b) and a >= 0.0:
                pairs.append(((b - a) * 1e3, n))
    return weighted_percentile(pairs, q)


def output_tok_s(run) -> Optional[float]:
    n = sum(c.n_seqs for c in run.calls
            if c.kind in ("start_batch", "decode_step")
            and in_window(run, c.end))
    return n / run.seconds if n else None


def calls(run, kind: str) -> List:
    return [c for c in run.calls if c.kind == kind and in_window(run, c.end)]


def mean_ms(run, kind: str) -> Optional[float]:
    cs = calls(run, kind)
    return 1e3 * sum(c.end - c.begin for c in cs) / len(cs) if cs else None


def compiles(run) -> float:
    return float(sum(1 for t in run.compile_times if 0.0 <= t <= run.seconds))


def tail_tokens(c) -> int:
    return sum(c.plen - h * c.block_size for h in c.hit_counts)


def prefix_hit_share(run) -> Optional[float]:
    cs = calls(run, "start_batch")
    total = sum(c.plen * len(c.hit_counts) for c in cs)
    hit = sum(h * c.block_size for c in cs for h in c.hit_counts)
    return 100.0 * hit / total if total else None


def prefill_ms_per_ktok(run) -> Optional[float]:
    cs = calls(run, "start_batch")
    toks = sum(tail_tokens(c) for c in cs)
    return 1e3 * sum(c.end - c.begin for c in cs) / (toks / 1e3) \
        if toks else None


def batch_seqs_mean(run) -> Optional[float]:
    cs = calls(run, "decode_step")
    return sum(c.n_seqs for c in cs) / len(cs) if cs else None


# ------------------------------------------------------------ traced span

def traced_calls(run, kind: Optional[str] = None) -> List:
    if run.trace is None or run.trace_span is None:
        return []
    b, e = run.trace_span
    return [c for c in run.calls if b <= c.begin and c.end <= e
            and (kind is None or c.kind == kind)]


def call_flops(s: flops.Sizes, c) -> float:
    if c.kind == "start_batch":
        return sum(flops.prefill_flops(s, h * c.block_size, c.plen)
                   for h in c.hit_counts)
    if c.kind == "decode_step":
        return sum(flops.token_flops(s, p, True) for p in c.positions)
    return 0.0


def mfu(run) -> Optional[float]:
    cs = traced_calls(run)
    work = sum(call_flops(run.sizes, c) for c in cs)
    if not work or not run.trace["window_s"]:
        return None
    return 100.0 * work / run.trace["window_s"] / run.peaks.bf16_flops


def idle_share(run) -> Optional[float]:
    if run.trace is None or not run.trace["window_s"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def decode_roofline(run) -> Optional[float]:
    """Least time of the traced decode steps over their device time (the
    decode step programs' module time)."""
    cs = traced_calls(run, "decode_step")
    dev = sum(v for k, v in run.trace["module_s"].items()
              if "decode_step" in k) if run.trace else 0.0
    if not cs or not dev:
        return None
    least = sum(flops.least_time(*flops.decode_step_cost(run.sizes,
                                                         c.positions),
                                 run.peaks.bf16_flops,
                                 run.peaks.hbm_bytes_per_s)[0] for c in cs)
    return 100.0 * least / dev


def dequant_matmul_roofline(run) -> Optional[float]:
    """Least time of every traced ``dequant_matmul`` call, from the shapes
    of the forwards the traced calls ran, over the kernel's device time."""
    if run.trace is None or run.sizes.weights != "int8":
        return None
    # the kernel's own op (the custom call the Pallas kernel lowers to,
    # named after its jit), not the pads and reshapes in its named scope
    dev = sum(v for k, v in run.trace["op_s"].items()
              if "dequant_matmul" in k)
    if not dev:
        return None
    least = 0.0
    for c in traced_calls(run):
        if c.kind == "decode_step":
            rows = [c.n_seqs]
        elif c.kind == "start_batch":
            groups = {}
            for h in c.hit_counts:
                groups[h] = groups.get(h, 0) + 1
            rows = [n * (c.plen - h * c.block_size)
                    for h, n in groups.items()]
        else:
            continue
        for m in rows:
            for call in flops.forward_dequant_calls(run.sizes, m):
                least += flops.least_time(
                    *flops.dequant_matmul_cost(call["m"], call["k"],
                                               call["n"]),
                    run.peaks.bf16_flops, run.peaks.hbm_bytes_per_s)[0]
    return 100.0 * least / dev
