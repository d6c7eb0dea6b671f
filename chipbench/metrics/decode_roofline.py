"""Decode step: roofline least time of the traced decode steps (weights once, live KV once) over their device time, %."""
from chipbench import readers


def read(run):
    return readers.decode_roofline(run)
