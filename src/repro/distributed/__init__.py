"""Distribution layer: mesh conventions, chunked collectives, cross-pod sync."""
from repro.distributed.chunked import (
    ag_matmul,
    chunked_all_gather,
    chunked_all_reduce,
    chunked_reduce_scatter,
    default_n_chunks,
    matmul_rs,
)
from repro.distributed.fsdp import cross_pod_mean, manual_pod
from repro.distributed.mesh import (
    DATA, MODEL, POD, MeshPlan, axis_size, batch_spec, shard, spec,
)

__all__ = [
    "ag_matmul", "chunked_all_gather", "chunked_all_reduce",
    "chunked_reduce_scatter", "default_n_chunks", "matmul_rs",
    "cross_pod_mean", "manual_pod",
    "DATA", "MODEL", "POD", "MeshPlan", "axis_size", "batch_spec",
    "shard", "spec",
]
