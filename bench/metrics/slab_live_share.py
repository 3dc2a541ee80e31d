"""Scheduler: slab rows carrying a token over rows computed."""
from bench import readers


def read(record):
    return readers.slab_live_share(record)
