"""Router: mean host ms per route_batch call in the window."""
from chipbench import readers


def read(run):
    return readers.mean_ms(run, "route_batch")
