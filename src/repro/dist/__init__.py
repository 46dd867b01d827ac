"""repro.dist — GSPMD + shard_map distribution layer.

  sharding     param/state/batch PartitionSpec rules (mesh layout contract)
  collectives  coded_matmul_shardmap: explicit per-device coded GEMM whose
               parity decode crosses the `model` axis (all_gather + local
               subtract — the paper's master/worker message flow)
  pipeline     pipeline_apply: GPipe microbatching over the `pod` axis
"""
from repro.dist.collectives import coded_matmul_shardmap
from repro.dist.pipeline import pipeline_apply
from repro.dist.sharding import (batch_axes, batch_spec, param_shardings,
                                 param_specs, state_specs)

__all__ = [
    "batch_axes",
    "batch_spec",
    "coded_matmul_shardmap",
    "param_shardings",
    "param_specs",
    "pipeline_apply",
    "state_specs",
]
