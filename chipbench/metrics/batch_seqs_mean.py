"""Scheduler: mean sequences per decode_step call in the window."""
from chipbench import readers


def read(run):
    return readers.batch_seqs_mean(run)
