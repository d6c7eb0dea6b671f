"""Model step: FLOPs the algorithm needs for every token prefilled and decoded in the traced span, over the span and the bf16 peak, %."""
from chipbench import readers


def read(run):
    return readers.mfu(run)
