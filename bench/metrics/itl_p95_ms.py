"""95th percentile of every gap between consecutive tokens of a request, as the client saw them."""
from bench import readers


def read(record):
    return readers.percentile(readers.itl_ms(readers.win(record)), 95)
