"""Device: share of the traced span in which no operation ran on the device, %."""
from chipbench import readers


def read(run):
    return readers.idle_share(run)
