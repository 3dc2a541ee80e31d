"""Model step: useful model operations in the window over the window times the chip's peak."""
from bench import readers


def read(record):
    return readers.mfu(record)
