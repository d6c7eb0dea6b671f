"""Scheduler: 90th percentile of the wait from a request's due time to the start of the prefill that holds it, ms (censored at the window's end)."""
from chipbench import readers


def read(run):
    return readers.queue_wait_p(run, 90)
