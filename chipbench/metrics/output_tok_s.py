"""Output tokens emitted in the window over the window's seconds."""
from chipbench import readers


def read(run):
    return readers.output_tok_s(run)
