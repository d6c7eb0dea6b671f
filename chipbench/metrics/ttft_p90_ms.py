"""90th percentile of time to first token, ms: from each request's due time to the end of the prefill that sampled it, over every request due in the window; one with no first token by the window's end enters with its age then."""
from chipbench import readers


def read(run):
    return readers.ttft_p(run, 90)
