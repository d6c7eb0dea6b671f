"""Decode step: roofline least time of the traced decode steps (replicated weights and the held experts hit once, latent context once) over their device time, %."""
from chipbench import readers_mla


def read(run):
    return readers_mla.decode_roofline(run, "deepseek-v2-lite-ep4")
