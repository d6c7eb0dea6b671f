"""95th percentile of the gaps between consecutive tokens of every sequence, ms, over the gaps that end in the window."""
from chipbench import readers


def read(run):
    return readers.tbt_p(run, 95)
