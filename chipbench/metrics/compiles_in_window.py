"""XLA compilations and compile-cache loads inside the window."""
from chipbench import readers


def read(run):
    return readers.compiles(run)
