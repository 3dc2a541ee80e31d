"""Engine: time per device iteration of the window."""
from bench import readers


def read(record):
    return readers.iter_ms(record)
