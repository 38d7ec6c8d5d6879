"""Cross-pod gradient synchronization helpers.

Two gradient-sync paths, mirroring the paper's baseline-vs-chunked pair:

  * **auto** (the un-chunked baseline): batch is sharded over (pod, data) in
    pjit; autodiff+GSPMD emit one monolithic all-reduce per gradient tensor
    spanning both axes. This corresponds to Globus moving a large file as a
    single stream.
  * **chunked** (the paper's contribution): the entire train step runs inside
    ``manual_pod`` — shard_map manual over the *pod* axis only, data/model
    axes left to GSPMD. Per-pod partial gradients are synchronized explicitly
    with ``cross_pod_mean``: a bandwidth-optimal reduce-scatter+all-gather
    ring whose messages are cut into planner-sized chunks, pipelining the
    slow, WAN-like DCN hop (DESIGN.md §2) and letting the optimizer math that
    consumes each chunk overlap subsequent chunk transfers.

The per-leaf chunk count follows ``core.chunker``'s rule transposed to the
interconnect: >= ~1 MiB per message, at most ``pipeline_depth`` chunks.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh

from repro.distributed import chunked as C
from repro.distributed.mesh import POD, axis_size


def cross_pod_mean(tree: Any, n_pods: int, *, n_chunks: int = 4) -> Any:
    """Chunked mean-all-reduce of a gradient pytree over the pod axis.

    Call *inside* a ``manual_pod`` region. Chunk count is clamped per-leaf so
    small tensors ship whole (the paper: chunking only pays for large files)
    while large tensors are pipelined in up to ``n_chunks`` ring messages.
    """
    if n_pods == 1:
        return tree

    def leaf(g):
        nc = min(n_chunks, C.default_n_chunks(g.size * g.dtype.itemsize))
        return C.chunked_all_reduce(g, POD, n_pods, n_chunks=nc) / n_pods

    return jax.tree.map(leaf, tree)


def manual_pod(fn, mesh: Mesh, *, in_specs, out_specs):
    """shard_map ``fn`` manually over POD only; data/model stay GSPMD-auto.

    With no pod axis in the mesh this is the identity wrapper, so the same
    train-step code serves single-pod and multi-pod launches.
    """
    if axis_size(mesh, POD) == 1:
        return fn
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        axis_names={POD}, check_vma=False,
    )
