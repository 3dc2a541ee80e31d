"""Device: share of the traced window with no operation on the device."""
from bench import readers


def read(record):
    return readers.device_idle(record)
