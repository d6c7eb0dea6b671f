"""Model step: FLOPs the algorithm needs (absorbed latent attention, held experts' expected pairs) for every token prefilled and decoded in the traced span, over the span and the bf16 peak, %."""
from chipbench import readers_mla


def read(run):
    return readers_mla.mfu(run, "deepseek-v2-lite-ep4")
