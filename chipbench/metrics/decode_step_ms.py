"""Backend decode: mean wall ms per decode_step call in the window."""
from chipbench import readers


def read(run):
    return readers.mean_ms(run, "decode_step")
