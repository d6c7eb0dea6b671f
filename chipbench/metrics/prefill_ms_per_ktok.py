"""Backend prefill: wall ms of start_batch per 1,000 prompt tokens actually prefilled."""
from chipbench import readers


def read(run):
    return readers.prefill_ms_per_ktok(run)
