"""Scheduler: median of admission minus due time over requests due in the window."""
from bench import readers


def read(record):
    return readers.percentile(readers.queue_wait_ms(readers.win(record)), 50)
