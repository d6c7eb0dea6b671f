"""Readers of the latent-attention, expert-parallel cells' device work
(`chipbench.flops_mla` counts it). `harness.Run` does not carry its
configuration, so each reads the sizes of the configuration it is
written for by name."""
from __future__ import annotations

from typing import Optional

from chipbench import flops, readers, registry
from chipbench.flops_mla import (MLASizes, decode_step_cost, prefill_flops,
                                 token_flops)
from chipbench.tracefile import seconds_matching


def sizes(config: str) -> MLASizes:
    return MLASizes.of(registry.config(config))


def _least(run, cost) -> float:
    return flops.least_time(*cost, run.peaks.bf16_flops,
                            run.peaks.hbm_bytes_per_s)[0]


def _share(least: float, dev: float) -> Optional[float]:
    return 100.0 * least / dev if least and dev else None


def mfu(run, config: str) -> Optional[float]:
    """FLOPs the traced prefills and decode steps need, over the traced
    span and the bf16 peak, %."""
    s = sizes(config)
    work = 0.0
    for c in readers.traced_calls(run):
        if c.kind == "start_batch":
            work += sum(prefill_flops(s, h * c.block_size, c.plen)
                        for h in c.hit_counts)
        elif c.kind == "decode_step":
            work += sum(token_flops(s, p, True) for p in c.positions)
    if not work or not run.trace["window_s"]:
        return None
    return 100.0 * work / run.trace["window_s"] / run.peaks.bf16_flops


def decode_roofline(run, config: str) -> Optional[float]:
    """Least time of the traced decode steps (replicated weights and the
    held experts their tokens hit once, each sequence's latent context
    once) over the decode step programs' device time, %."""
    s = sizes(config)
    cs = readers.traced_calls(run, "decode_step")
    if not cs:
        return None
    dev = seconds_matching(run.trace["module_s"], "decode_step")
    return _share(sum(_least(run, decode_step_cost(s, c.positions))
                      for c in cs), dev)
