"""Public, jit-friendly wrappers over the Pallas integrity kernels.

Entry points:
  fingerprint_array(x)        -> (NBASES,) int32 residues of x's byte image
  fingerprint_and_copy(x)     -> (residues, copy) — single-pass mover kernel
  digest_of(x)                -> core.integrity.Digest (host convenience)
  matmul_with_digest(a, b)    -> (a @ b, residues of a) — fused consume+verify
  fingerprint_host_rows(rows) -> Digests of host byte rows, digested on device
                                 (the integrity engine's device backend)

Packing: any array is flattened and bitcast to little-endian int32 words
(verified identical to numpy ``.view``). Byte counts not divisible by 4 or by
the kernel tile are zero-padded; padding is divided back out with the modular
inverse of r^pad (GF(p) is a field), so the returned residues equal the digest
of the *true* byte stream — host `fingerprint_bytes` agrees bit-for-bit, which
is exactly what lets device-side chunk digests be verified against host-side
file digests in the checkpoint path.

``interpret=None`` everywhere resolves per platform
(``checksum.resolve_interpret``): interpreted on CPU, compiled on TPU.
"""
from __future__ import annotations

import functools
import gc
import time
import weakref
from typing import Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.integrity import BASES, EMPTY_DIGEST, NBASES, P, Digest
from repro.kernels import checksum as _ck
from repro.kernels import matmul_digest as _mm
from repro.obs.metrics import REGISTRY
from repro.obs.trace import NULL, Tracer


def _pow_mod(base: int, exp: int) -> int:
    return pow(int(base), int(exp), P)


def _to_words(x: jax.Array) -> tuple[jax.Array, int]:
    """Flatten + pack into int32 words (little-endian), zero-padding the tail.

    Sub-word dtypes are packed from a lane-dense (rows, per*128) view: the
    j-th element of every word is every per-th lane, shifted into place. A
    bitcast from an (n, per) view would be the same bytes, but the TPU tiles
    the last two dims by (8, 128), so that minor dim of 2 or 4 pads each
    element out to a whole tile row (a 0.4 GB bf16 leaf asks for 55 GB).
    """
    flat = x.reshape(-1)
    isz = flat.dtype.itemsize
    nbytes = flat.size * isz
    if isz == 4:
        return jax.lax.bitcast_convert_type(flat, jnp.int32), nbytes
    if isz not in (1, 2):
        raise NotImplementedError(f"unsupported itemsize {isz} for {flat.dtype}")
    per = 4 // isz                                   # elements per word
    codes = jax.lax.bitcast_convert_type(flat, {1: jnp.uint8, 2: jnp.uint16}[isz])
    pad = (-codes.size) % (per * _ck.LANES)
    if pad:
        codes = jnp.pad(codes, (0, pad))
    lanes = codes.reshape(-1, per * _ck.LANES).astype(jnp.uint32)
    words = lanes[:, 0::per]
    for j in range(1, per):
        words = words | (lanes[:, j::per] << (8 * isz * j))
    return jax.lax.bitcast_convert_type(words, jnp.int32).reshape(-1), nbytes


def _unpad_residues(res: jax.Array, padded_bytes: int, true_bytes: int) -> jax.Array:
    """Divide out the trailing zero padding: H_true = H_pad * r^-(pad)."""
    pad = padded_bytes - true_bytes
    if pad == 0:
        return res
    inv = jnp.asarray(
        [_pow_mod(_pow_mod(r, pad), P - 2) for r in BASES], dtype=jnp.int32
    )
    return (res * inv) % P


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def fingerprint_array(
    x: jax.Array, *, rows: int = _ck.ROWS, interpret: bool | None = None
) -> jax.Array:
    """Digest residues (NBASES,) int32 of an array's little-endian byte image."""
    words, nbytes = _to_words(x)
    tile = rows * _ck.LANES
    padw = (-words.size) % tile
    if words.size == 0:
        return jnp.zeros((NBASES,), jnp.int32)
    if padw:
        words = jnp.pad(words, (0, padw))
    res = _ck.checksum_words(words, rows=rows, interpret=interpret)
    return _unpad_residues(res, words.size * 4, nbytes)


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def fingerprint_and_copy(
    x: jax.Array, *, rows: int = _ck.ROWS, interpret: bool | None = None
) -> tuple[jax.Array, jax.Array]:
    """Single-HBM-pass mover: returns (residues, copy-of-x)."""
    words, nbytes = _to_words(x)
    tile = rows * _ck.LANES
    padw = (-words.size) % tile
    padded = jnp.pad(words, (0, padw)) if padw else words
    res, copy_words = _ck.checksum_copy_words(padded, rows=rows, interpret=interpret)
    res = _unpad_residues(res, padded.size * 4, nbytes)
    flat = x.reshape(-1)
    isz = flat.dtype.itemsize
    if isz == 4:
        copy = jax.lax.bitcast_convert_type(copy_words[: flat.size], x.dtype)
    else:
        n_units = (flat.size * isz + isz - 1) // isz
        unit = {2: jnp.uint16, 1: jnp.uint8}[isz]
        units = jax.lax.bitcast_convert_type(copy_words, unit).reshape(-1)[: flat.size]
        copy = jax.lax.bitcast_convert_type(units, x.dtype)
    return res, copy.reshape(x.shape)


def digest_of(x: jax.Array, *, interpret: bool | None = None) -> Digest:
    """Host-side Digest of a device array (residues via the Pallas kernel)."""
    res = np.asarray(fingerprint_array(x, interpret=interpret))
    nbytes = x.size * x.dtype.itemsize
    return Digest(tuple(int(v) for v in res), int(nbytes))


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def matmul_with_digest(
    a: jax.Array, b: jax.Array, *, bm: int = 128, bn: int = 128, bk: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused C = A @ B and digest of A (blocked order — see ref.blocked_view)."""
    return _mm.matmul_digest(a, b, bm=bm, bn=bn, bk=bk, interpret=interpret)


# ---------------------------------------------------------------------------
# host rows -> bucketed device dispatches (the served integrity path)
# ---------------------------------------------------------------------------
PIECE_BYTES = 8 * 1024 * 1024    # largest bucket; longer rows go as pieces
BATCH_BYTES = 32 * 1024 * 1024   # bytes per dispatch
BATCH_ROWS = 32                  # rows per dispatch for buckets <= 1 MiB
# every padded row length the device is handed: TILE_BYTES * 2^i
BUCKETS = tuple(_ck.TILE_BYTES << i
                for i in range((PIECE_BYTES // _ck.TILE_BYTES).bit_length()))

_checksum_many = jax.jit(_ck.checksum_many_words, static_argnames=("rows", "interpret"))

_M_DISPATCHES = REGISTRY.counter(
    "digest_dispatches_total",
    "device digest dispatches, by how the batch reached the device: read in "
    "place from the caller's row (view) or copied into a zeroed stage (staged)",
    ("path",))


def bucket_of(nbytes: int) -> int:
    """Smallest bucket holding ``nbytes`` (1 <= nbytes <= PIECE_BYTES)."""
    return next(b for b in BUCKETS if b >= nbytes)


def batch_rows(bucket: int) -> int:
    """The fixed row count k of every dispatch at ``bucket``."""
    return max(1, min(BATCH_ROWS, BATCH_BYTES // bucket))


def _row_view(rows: Sequence[np.ndarray], batch: list[tuple[int, int, int]],
              bucket: int, k: int) -> np.ndarray | None:
    """The batch as a zero-copy ``(k, bucket // 4)`` int32 view of its row,
    or ``None`` when it has to be staged: it is that view only when it holds
    k pieces that each fill the bucket, back to back in one C-contiguous row,
    starting at a 4-byte aligned address."""
    i, s, _n = batch[0]
    if len(batch) != k or any(piece != (i, s + m * bucket, bucket)
                              for m, piece in enumerate(batch)):
        return None
    row = rows[i]
    if not row.flags.c_contiguous or (row.ctypes.data + s) % 4:
        return None
    return row[s:s + k * bucket].view(np.int32).reshape(k, bucket // 4)


def _await_release(sent: weakref.ref, timeout_s: float = 10.0) -> None:
    """Wait until the runtime drops the host array a dispatch was sent from.

    It lets go once the transfer is done, from a thread of its own and
    without the GIL, so the reference may be queued for jax's gc callback a
    little after the residues are back; a young collection runs the callback.
    """
    deadline = time.perf_counter() + timeout_s
    while sent() is not None:
        gc.collect(0)
        if sent() is None:
            return
        if time.perf_counter() > deadline:
            raise RuntimeError(f"the runtime still holds a digest's host rows "
                               f"{timeout_s} s after its residues returned")
        time.sleep(1e-4)


@functools.lru_cache(maxsize=1 << 12)
def _unpad_vector(pad: int) -> np.ndarray:
    """r^-pad per base: divides ``pad`` trailing zero bytes back out."""
    vec = np.asarray([_pow_mod(_pow_mod(r, pad), P - 2) for r in BASES], np.int64)
    vec.flags.writeable = False                   # shared by every caller
    return vec


def fingerprint_host_rows(rows: Sequence[np.ndarray], *, tracer: Tracer = NULL,
                          task: str = "", lane: str = "", **span) -> list[Digest]:
    """Digests of 1-D uint8 host rows, every byte digested on the device.

    Rows are cut into pieces of at most ``PIECE_BYTES`` and handed over as
    int32 words, ``batch_rows(bucket)`` pieces per ``checksum_many_words``
    dispatch, where a piece's bucket is a power-of-two number of kernel tiles
    that holds it. A batch of k pieces that each fill the bucket, back to
    back in one C-contiguous row at a 4-byte aligned address, goes to the
    device as a view of that row, with no host copy. Every other batch is
    staged: zero-padded on the host into a fresh ``(k, bucket)`` array whose
    unused rows stay zero and are dropped. Either way the device sees at most
    ``len(BUCKETS)`` shapes, however ragged the input. The padding is divided
    back out exactly (the ``_unpad_residues`` identity) and a row's pieces
    merge by the merge law. ``digest_dispatches_total{path=view|staged}``
    counts the dispatches of each kind.

    The runtime reads a view from the caller's rows, which must stay alive
    and unwritten until this returns. By then the residues have been fetched
    (so the kernel has read its input), the device input is deleted, and the
    runtime has dropped its reference to the rows (``_await_release``): the
    caller may release, reuse or resize their buffers.

    Each dispatch records four ``cksum`` spans on ``tracer`` (``task``,
    ``lane``, and ``bucket``, ``rows`` plus ``span`` as args), each inside a
    ``jax.profiler.TraceAnnotation`` of its name: ``digest_stage`` (zeroed
    stage and copies, or nothing for a view; its ``staged_bytes`` arg is
    the stage's size, 0 for a view), ``digest_put`` (host-to-device array
    and kernel enqueue), ``digest_wait`` (the blocking fetch of the residues,
    then the device input deleted and, for a view, the runtime's reference to
    the rows awaited) and ``digest_unpad`` (unpadding, and merging the rows
    this dispatch completes). They time what the dispatch does anyway:
    nothing waits on the device for their sake.
    """
    todo: dict[int, list[tuple[int, int, int]]] = {}   # bucket -> (row, start, n)
    for i, r in enumerate(rows):
        for s in range(0, r.size, PIECE_BYTES):
            n = min(PIECE_BYTES, r.size - s)
            todo.setdefault(bucket_of(n), []).append((i, s, n))
    left = [-(-r.size // PIECE_BYTES) for r in rows]    # pieces still to digest
    parts: dict[tuple[int, int], Digest] = {}
    out = [EMPTY_DIGEST] * len(rows)
    for bucket, pieces in todo.items():
        k = batch_rows(bucket)
        for b0 in range(0, len(pieces), k):
            batch = pieces[b0:b0 + k]
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("digest_stage"):
                host = _row_view(rows, batch, bucket, k)
                staged_bytes = 0 if host is not None else k * bucket
                if staged_bytes:
                    stage = np.zeros((k, bucket), np.uint8)
                    for j, (i, s, n) in enumerate(batch):
                        stage[j, :n] = rows[i][s:s + n]
                    host = stage.view(np.int32)
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("digest_put"):
                words = jnp.asarray(host)
                res = _checksum_many(words)
            t2 = time.perf_counter()
            with jax.profiler.TraceAnnotation("digest_wait"):
                res = np.asarray(res)
                words.delete()                   # the kernel has read it
                sent = weakref.ref(host)
                del words, host
                if not staged_bytes:
                    _await_release(sent)
            t3 = time.perf_counter()
            with jax.profiler.TraceAnnotation("digest_unpad"):
                for j, (i, s, n) in enumerate(batch):
                    h = res[j].astype(np.int64) * _unpad_vector(bucket - n) % P
                    parts[(i, s)] = Digest(tuple(int(v) for v in h), n)
                    left[i] -= 1
                    if left[i] == 0:
                        d = EMPTY_DIGEST
                        for s2 in range(0, rows[i].size, PIECE_BYTES):
                            d = d.merge(parts.pop((i, s2)))
                        out[i] = d
            t4 = time.perf_counter()
            _M_DISPATCHES.inc(path="staged" if staged_bytes else "view")
            tracer.add("digest_stage", "cksum", t0, t1, task=task, lane=lane,
                       bucket=bucket, rows=len(batch), staged_bytes=staged_bytes, **span)
            for name, a, b in (("digest_put", t1, t2), ("digest_wait", t2, t3),
                               ("digest_unpad", t3, t4)):
                tracer.add(name, "cksum", a, b, task=task, lane=lane,
                           bucket=bucket, rows=len(batch), **span)
    return out
