"""Seconds from process start to the window's opening: loading, weights, compilation and warm-up."""


def read(record):
    return record["setup_s"]
