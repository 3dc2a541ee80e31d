"""Pallas kernels: the paper's custom PUs on TPU (paged attention on the
serving path; flash attention, MM PU, RMSNorm and RWKV-6 alongside).

Every kernel picks its Pallas mode here, from the backend that runs it:
compiled by Mosaic on a TPU, the Pallas interpreter everywhere else (the
CPU test suite).  It is not an option: a kernel that interpreted on the
chip would silently run as plain XLA ops.
"""
from __future__ import annotations

import jax


def interpret_mode() -> bool:
    """True unless the default backend is a TPU."""
    return jax.default_backend() != "tpu"
