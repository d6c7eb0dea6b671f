"""Prefix pool: share of the prompt tokens of the prefills started in the window that came from the pool, %."""
from chipbench import readers


def read(run):
    return readers.prefix_hit_share(run)
