"""85th percentile over every request due in the window of first token minus due time."""
from bench import readers


def read(record):
    return readers.percentile(readers.ttft_ms(readers.win(record)), 85)
