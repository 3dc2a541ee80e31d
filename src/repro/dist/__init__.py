"""repro.dist — the execution layer that maps an ExecutionPlan onto devices.

core/plan.py *derives* the accelerator instance (SPATIAL vs TEMPORAL stage
modes, P_ATB head sharding, remat/microbatching); this package *executes* it:

  sharding.py    PartitionSpecs per parameter/cache/activation path
                 (Megatron orientation + divisibility safety net)
  collectives.py manual shard_map collectives (ring overlap matmul,
                 Megatron-SP reduce-scatter, compressed gradient psum)
  pipeline.py    TEMPORAL serial-PRG microbatch pipelining over the pod axis

Since PR 2 all three are live in launch/train.py: pipeline via
plan.pod_role, compressed_psum via plan.grad_compression, and the SP
collectives via plan.seq_parallel_acts (docs/ARCHITECTURE.md).
"""
from repro.dist.collectives import (
    compressed_psum,
    overlap_all_gather_matmul,
    ring_gather_matmul,
    seq_scatter,
)
from repro.dist.pipeline import bubble_fraction, pipeline_forward
from repro.dist.sharding import Shardings

__all__ = [
    "Shardings",
    "overlap_all_gather_matmul",
    "ring_gather_matmul",
    "seq_scatter",
    "compressed_psum",
    "bubble_fraction",
    "pipeline_forward",
]
