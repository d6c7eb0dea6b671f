"""Latent attention: roofline least time of the traced decode steps' latent attention (every layer's scores and context over each sequence's latent context, read once) over the device time of the paged latent decode kernel's ops, %; None when no such op ran."""
from chipbench import flops, readers
from chipbench.flops_mla import attend_flops
from chipbench.readers_mla import sizes

KERNEL = "paged_latent_decode"


def read(run):
    if run.trace is None:
        return None
    dev = sum(v for k, v in run.trace["op_s"].items() if KERNEL in k)
    if not dev:
        return None
    s = sizes("deepseek-v2-lite-ep4")
    least = 0.0
    for c in readers.traced_calls(run, "decode_step"):
        work = sum(s.layers * attend_flops(s, p + 1) for p in c.positions)
        nbytes = sum((p + 1) * s.latent_bytes_per_token for p in c.positions)
        least += flops.least_time(work, nbytes, run.peaks.bf16_flops,
                                  run.peaks.hbm_bytes_per_s)[0]
    return 100.0 * least / dev
