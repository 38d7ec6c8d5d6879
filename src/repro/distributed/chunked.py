"""Chunked collectives — client-driven chunking applied to ICI/DCN transfers.

The paper's mechanism, transposed to a TPU mesh (DESIGN.md §2): a large
tensor moving across an axis is cut into chunks that travel as independent
``ppermute`` ring steps, so (a) every link hop carries fine-grained messages
that the scheduler can overlap with compute, and (b) a consumer (matmul) can
start on chunk k-1 while chunk k is in flight — the Fig. 4 transfer/verify
overlap with the MXU playing the role of the checksum pipeline.

All functions are *manual-SPMD*: call them inside ``jax.shard_map``. The
monolithic baselines (``jax.lax.all_gather`` / ``psum`` / ``psum_scatter``)
are what the paper's un-chunked Globus corresponds to; benchmarks and the
§Perf hillclimb compare the two by collective schedule in the lowered HLO.

Chunk-count choice mirrors ``core.chunker``: enough chunks to keep the ring
pipelined (>= pipeline_depth per hop), but each message large enough to
amortize per-ppermute latency (~1 us on ICI => >= ~1 MiB messages).
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp


def _ring_perm(axis_size: int, reverse: bool = False):
    if reverse:
        return [((i + 1) % axis_size, i) for i in range(axis_size)]
    return [(i, (i + 1) % axis_size) for i in range(axis_size)]


def default_n_chunks(nbytes: int, *, pipeline_depth: int = 4, min_chunk_bytes: int = 1 << 20) -> int:
    """Paper §3.1 heuristic at ICI scale: depth chunks, >= 1 MiB messages."""
    if nbytes <= min_chunk_bytes:
        return 1
    return max(1, min(pipeline_depth, nbytes // min_chunk_bytes))


# ---------------------------------------------------------------------------
# all-gather
# ---------------------------------------------------------------------------
def chunked_all_gather(
    x: jax.Array, axis_name: str, axis_size: int, *, n_chunks: int = 4
) -> jax.Array:
    """Ring all-gather of the local shard, moved in ``n_chunks`` sub-messages.

    x: (s, ...) local shard -> (axis_size * s, ...), identical to
    jax.lax.all_gather(x, axis_name, tiled=True) (the monolithic baseline).
    """
    s = x.shape[0]
    if n_chunks > 1 and s % n_chunks != 0:
        n_chunks = 1  # fall back rather than mis-chunk
    idx = jax.lax.axis_index(axis_name)
    perm = _ring_perm(axis_size)

    pieces = jnp.split(x, n_chunks, axis=0) if n_chunks > 1 else [x]
    out_rows = axis_size * s
    out = jnp.zeros((out_rows,) + x.shape[1:], x.dtype)

    # Interleave the chunk rings: all chunks advance one hop per "step", so at
    # any instant n_chunks fine messages are in flight on each link instead of
    # one monolithic message — the ERET/ESTO pipelining of §3.1.
    bufs = list(pieces)
    cs = s // n_chunks
    for c, piece in enumerate(pieces):
        start = idx * s + c * cs
        out = jax.lax.dynamic_update_slice_in_dim(out, piece, start, axis=0)
    for step in range(1, axis_size):
        src = (idx - step) % axis_size
        for c in range(n_chunks):
            bufs[c] = jax.lax.ppermute(bufs[c], axis_name, perm)
            start = src * s + c * cs
            out = jax.lax.dynamic_update_slice_in_dim(out, bufs[c], start, axis=0)
    return out


# ---------------------------------------------------------------------------
# reduce-scatter
# ---------------------------------------------------------------------------
def chunked_reduce_scatter(
    x: jax.Array, axis_name: str, axis_size: int, *, n_chunks: int = 4
) -> jax.Array:
    """Ring reduce-scatter: x (A*s, ...) on every device -> (s, ...) summed shard.

    Equivalent to jax.lax.psum_scatter(x, axis_name, tiled=True).
    """
    rows = x.shape[0]
    assert rows % axis_size == 0, (rows, axis_size)
    s = rows // axis_size
    if n_chunks > 1 and s % n_chunks != 0:
        n_chunks = 1
    idx = jax.lax.axis_index(axis_name)
    perm = _ring_perm(axis_size)
    cs = s // n_chunks

    def block(owner: jax.Array, c: int) -> jax.Array:
        return jax.lax.dynamic_slice_in_dim(x, owner * s + c * cs, cs, axis=0)

    # Ring invariant (derivation in tests/test_chunked_collectives.py): at
    # step t rank r receives the running partial for block (r-1-t) mod A and
    # adds its local contribution; after A-1 steps rank r holds block r,
    # summed over all ranks — matching psum_scatter(tiled=True).
    own0 = jnp.mod(idx - 1, axis_size)
    acc = [block(own0, c) for c in range(n_chunks)]
    for step in range(1, axis_size):
        own = jnp.mod(idx - 1 - step, axis_size)
        for c in range(n_chunks):
            acc[c] = jax.lax.ppermute(acc[c], axis_name, perm)
            acc[c] = acc[c] + block(own, c)
    return jnp.concatenate(acc, axis=0) if n_chunks > 1 else acc[0]


def chunked_all_reduce(
    x: jax.Array, axis_name: str, axis_size: int, *, n_chunks: int = 4
) -> jax.Array:
    """Bandwidth-optimal all-reduce = chunked reduce-scatter + chunked all-gather.

    Equivalent to jax.lax.psum(x, axis_name). This is the pod-axis gradient
    synchronization path: the cross-pod (DCN) hop is the slow WAN-like link
    where the paper's chunking pays most.
    """
    shape = x.shape
    flat = x.reshape(-1)
    groups = axis_size * n_chunks
    pad = (-flat.size) % groups
    if pad:
        flat = jnp.pad(flat, (0, pad))
    mat = flat.reshape(groups, -1)                      # (A*n_chunks, m)
    shard = chunked_reduce_scatter(mat, axis_name, axis_size, n_chunks=n_chunks)
    full = chunked_all_gather(shard, axis_name, axis_size, n_chunks=n_chunks)
    return full.reshape(-1)[: x.size].reshape(shape)


# ---------------------------------------------------------------------------
# overlapped all-gather matmul (collective matmul)
# ---------------------------------------------------------------------------
def ag_matmul(
    x: jax.Array, w_shard: jax.Array, axis_name: str, axis_size: int
) -> jax.Array:
    """y = x @ all_gather(w_shard) with transfer/compute overlap.

    x: (B, K) replicated on the axis; w_shard: (K/A, N) local rows of W.
    Each step multiplies the weight block currently resident while the ring
    permute moves the next one — the MXU consumes chunk k-1 as chunk k moves,
    the paper's Fig. 4 overlap with compute in place of checksumming. The
    weight blocks are the chunks; chunk size is fixed by the FSDP shard.
    """
    B, K = x.shape
    kA, N = w_shard.shape
    assert kA * axis_size == K, (x.shape, w_shard.shape, axis_size)
    idx = jax.lax.axis_index(axis_name)
    perm = _ring_perm(axis_size, reverse=True)  # pull blocks from the right

    def x_block(owner: jax.Array) -> jax.Array:
        return jax.lax.dynamic_slice_in_dim(x, owner * kA, kA, axis=1)

    acc = x_block(idx) @ w_shard
    buf = w_shard
    for step in range(1, axis_size):
        buf = jax.lax.ppermute(buf, axis_name, perm)
        owner = (idx + step) % axis_size
        acc = acc + x_block(owner) @ buf
    return acc


def matmul_rs(
    x: jax.Array, w: jax.Array, axis_name: str, axis_size: int, *, n_chunks: int = 1
) -> jax.Array:
    """y_shard = reduce_scatter(x_partial @ w_partial) — the row-parallel pair.

    x: (B, K/A) local columns; w: (K/A, N) local rows; output (B/A, N).
    Partial products are reduce-scattered chunk-wise so early output blocks
    ship while later blocks are still in the MXU.
    """
    part = x @ w                                    # (B, N) partial sum
    B = part.shape[0]
    assert B % axis_size == 0
    return chunked_reduce_scatter(part, axis_name, axis_size, n_chunks=n_chunks)
