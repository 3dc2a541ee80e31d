"""Kernel: the paged-attention kernel's share of its roofline."""
from bench import readers


def read(record):
    return readers.roofline(record)
