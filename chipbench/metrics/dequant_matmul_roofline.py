"""Kernel dequant_matmul: roofline least time of its traced calls, from their shapes, over its device time in the trace, %."""
from chipbench import readers


def read(run):
    return readers.dequant_matmul_roofline(run)
