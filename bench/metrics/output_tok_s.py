"""Output tokens emitted in the window over the window's seconds."""
from bench import readers


def read(record):
    return readers.output_tok_s(record)
