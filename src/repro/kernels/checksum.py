"""Pallas TPU kernels for mergeable integrity fingerprints.

Hardware adaptation (DESIGN.md §2): MD5's sequential 64-byte chain is replaced
by a degree-weighted polynomial fingerprint over GF(46337) — see
``repro.core.integrity`` for the algebra. Everything here is int32: the prime
was chosen so that every product of residues fits a signed 32-bit lane, i.e.
the whole digest runs on the TPU VPU (8x128 int32 lanes) with no 64-bit
emulation.

Kernels:
  * ``checksum_kernel``       — digest of an int32 word stream.
  * ``checksum_copy_kernel``  — data mover: copies the stream AND digests it in
    the same HBM pass (the paper's "checksum while first reading the file",
    Fig. 4 caption) — one read instead of two.

Execution: ``interpret=None`` (the default everywhere) resolves from the
platform JAX runs on — interpreted on ``cpu`` (how the tests validate the
kernel bodies), compiled on ``tpu``; any other platform is an error, never a
silent interpreter run.

Tiling: the grid walks (ROWS, 128)-word tiles; TPU grids execute sequentially
on a core, so the running digest accumulates in the output ref across steps
(init at step 0). Per-tile weight tables live in VMEM and are reused every
step (index_map pins them to block 0). The byte-plane factorization keeps the
table at (NBASES, ROWS, 128) int32 — ~128 KiB at ROWS=64 — instead of 4x that:
byte k of word m sits at stream position 4m+k, so its weight is
``W0[m] * r^-k`` with W0[m] = r^(T-1-4m); the three extra scalar multiplies
per plane are free next to the loads.

Numeric safety rails (asserted in tests over full shape/dtype sweeps):
  byte*weight <= 255*46336 = 1.18e7; 128-lane sum <= 1.51e9 < 2^31;
  row-sum of residues <= ROWS*P; residue*residue <= (P-1)^2 = 2.147e9 < 2^31.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.integrity import BASES, NBASES, P

ROWS = 64           # words per tile row-block: tile = ROWS*128 words = 32 KiB
LANES = 128
TILE_WORDS = ROWS * LANES
TILE_BYTES = 4 * TILE_WORDS


def _pow_mod(base: int, exp: int) -> int:
    return pow(int(base), int(exp), P)


def resolve_interpret(interpret: bool | None) -> bool:
    """Interpret on ``cpu``, compile on ``tpu``; an explicit bool wins.

    Any other platform raises: a digest kernel must never fall back to the
    interpreter (or to a reference path) on an accelerator.
    """
    if interpret is not None:
        return bool(interpret)
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"no Pallas digest kernel for platform {platform!r}")


@functools.lru_cache(maxsize=None)
def _tables(rows: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(W0, rinv, rpow): word weights r^(T-1-4m), byte-plane r^-k, tile r^T."""
    tile_words = rows * LANES
    tile_bytes = 4 * tile_words
    w0 = np.empty((NBASES, rows, LANES), np.int32)
    rinv = np.empty((NBASES, 4), np.int32)
    rpow = np.empty((NBASES, 1), np.int32)
    for b, r in enumerate(BASES):
        r4 = _pow_mod(r, 4)
        r4inv = _pow_mod(r4, P - 2)
        acc = _pow_mod(r, tile_bytes - 1)          # weight of word m=0
        flat = np.empty(tile_words, np.int64)
        for m in range(tile_words):
            flat[m] = acc
            acc = (acc * r4inv) % P
        w0[b] = flat.reshape(rows, LANES)
        rinvk = _pow_mod(r, P - 2)
        rinv[b] = [1, rinvk, (rinvk * rinvk) % P, (rinvk * rinvk % P) * rinvk % P]
        rpow[b, 0] = _pow_mod(r, tile_bytes)
    return w0, rinv, rpow


def _plane_hash(words: jax.Array, w0: jax.Array, rinv_row: jax.Array) -> jax.Array:
    """Tile hash for one base given its weight table. words: (R,128) int32."""
    th = jnp.int32(0)
    for k in range(4):
        plane = jnp.bitwise_and(jax.lax.shift_right_logical(words, 8 * k), 255)
        s = jnp.sum(plane * w0, axis=1) % P        # (R,) — lane fold, <2^31
        s = jnp.sum(s) % P                          # row fold, R*P < 2^31
        th = (th + s * rinv_row[k]) % P             # plane shift by r^-k
    return th


def _checksum_kernel(words_ref, w0_ref, rinv_ref, rpow_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros((1, NBASES), jnp.int32)

    words = words_ref[...]
    acc = out_ref[...]
    new = []
    for b in range(NBASES):
        th = _plane_hash(words, w0_ref[b], rinv_ref[b])
        new.append((acc[0, b] * rpow_ref[b, 0] + th) % P)  # H <- H*r^T + h_tile
    out_ref[...] = jnp.stack(new)[None, :]


def _checksum_copy_kernel(words_ref, w0_ref, rinv_ref, rpow_ref, out_ref, copy_ref):
    copy_ref[...] = words_ref[...]                 # the ESTO write ...
    _checksum_kernel(words_ref, w0_ref, rinv_ref, rpow_ref, out_ref)  # ... + inline digest


def _common_specs(rows: int):
    return [
        pl.BlockSpec((rows, LANES), lambda i: (i, 0)),          # data tile
        pl.BlockSpec((NBASES, rows, LANES), lambda i: (0, 0, 0)),  # weights (pinned)
        pl.BlockSpec((NBASES, 4), lambda i: (0, 0)),            # r^-k scalars
        pl.BlockSpec((NBASES, 1), lambda i: (0, 0)),            # r^T scalar
    ]


def checksum_words(
    words: jax.Array, *, rows: int = ROWS, interpret: bool | None = None
) -> jax.Array:
    """Digest residues (NBASES,) int32 of an int32 word stream.

    ``words`` must be 1-D int32 with size % (rows*128) == 0 (the ops.py wrapper
    handles padding + pad correction). ``interpret`` follows
    ``resolve_interpret``: the interpreter on CPU, the compiled kernel on TPU.
    """
    assert words.ndim == 1 and words.dtype == jnp.int32, (words.shape, words.dtype)
    tile = rows * LANES
    assert words.size % tile == 0 and words.size > 0, words.size
    w0, rinv, rpow = _tables(rows)
    grid = (words.size // tile,)
    out = pl.pallas_call(
        _checksum_kernel,
        grid=grid,
        in_specs=_common_specs(rows),
        out_specs=pl.BlockSpec((1, NBASES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, NBASES), jnp.int32),
        interpret=resolve_interpret(interpret),
        name="chunk_checksum",
    )(words.reshape(-1, LANES), jnp.asarray(w0), jnp.asarray(rinv), jnp.asarray(rpow))
    return out[0]


def _checksum_many_kernel(words_ref, w0_ref, rinv_ref, rpow_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros((1, NBASES), jnp.int32)

    words = words_ref[0]                           # (rows, LANES)
    acc = out_ref[...]
    new = []
    for b in range(NBASES):
        th = _plane_hash(words, w0_ref[b], rinv_ref[b])
        new.append((acc[0, b] * rpow_ref[b, 0] + th) % P)
    out_ref[...] = jnp.stack(new)[None, :]


def checksum_many_words(
    words2d: jax.Array, *, rows: int = ROWS, interpret: bool | None = None
) -> jax.Array:
    """Digests of k equal-length int32 word streams in ONE kernel dispatch.

    ``words2d`` is (k, n_words) with n_words % (rows*128) == 0. The grid is
    (k, tiles): the row axis is the batch, the tile axis walks each stream
    sequentially (TPU grids execute in row-major order, so the per-stream
    running digest accumulates in its output row, re-initialized whenever the
    tile index wraps to 0). This is the accelerator side of the fused
    IntegrityEngine drain: one dispatch per drain batch instead of one per
    chunk — the same per-call amortization ``fingerprint_rows`` does for the
    host GEMM path, with the weight tables pinned in VMEM across the whole
    batch. Returns (k, NBASES) int32 residues.

    The output is laid out (k, 1, NBASES) with one (1, NBASES) block per
    stream: the TPU compiler only accepts blocks whose last two dims equal
    the array's (or divide by 8 and 128), which a (1, NBASES) block of a
    (k, NBASES) array does not.
    """
    assert words2d.ndim == 2 and words2d.dtype == jnp.int32, (words2d.shape, words2d.dtype)
    k, n = words2d.shape
    tile = rows * LANES
    assert n % tile == 0 and n > 0 and k > 0, (k, n)
    w0, rinv, rpow = _tables(rows)
    grid = (k, n // tile)
    out = pl.pallas_call(
        _checksum_many_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, rows, LANES), lambda i, j: (i, j, 0)),     # stream tile
            pl.BlockSpec((NBASES, rows, LANES), lambda i, j: (0, 0, 0)),  # weights (pinned)
            pl.BlockSpec((NBASES, 4), lambda i, j: (0, 0)),             # r^-k scalars
            pl.BlockSpec((NBASES, 1), lambda i, j: (0, 0)),             # r^T scalar
        ],
        out_specs=pl.BlockSpec((None, 1, NBASES), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((k, 1, NBASES), jnp.int32),
        interpret=resolve_interpret(interpret),
        name="chunk_checksum_many",
    )(words2d.reshape(k, -1, LANES), jnp.asarray(w0), jnp.asarray(rinv), jnp.asarray(rpow))
    return out[:, 0, :]


def checksum_copy_words(
    words: jax.Array, *, rows: int = ROWS, interpret: bool | None = None
) -> tuple[jax.Array, jax.Array]:
    """Copy an int32 word stream while digesting it (one pass over HBM).

    Returns (digest_residues (NBASES,), copy). The copy output aliases nothing:
    this is the chunk landing in its destination buffer with the integrity
    check folded into the same data movement, paper Fig. 4's overlap taken to
    its limit (zero extra read).
    """
    assert words.ndim == 1 and words.dtype == jnp.int32
    tile = rows * LANES
    assert words.size % tile == 0 and words.size > 0
    w0, rinv, rpow = _tables(rows)
    grid = (words.size // tile,)
    digest, copy = pl.pallas_call(
        _checksum_copy_kernel,
        grid=grid,
        in_specs=_common_specs(rows),
        out_specs=[
            pl.BlockSpec((1, NBASES), lambda i: (0, 0)),
            pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, NBASES), jnp.int32),
            jax.ShapeDtypeStruct((words.size // LANES, LANES), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
        name="chunk_checksum_copy",
    )(words.reshape(-1, LANES), jnp.asarray(w0), jnp.asarray(rinv), jnp.asarray(rpow))
    return digest[0], copy.reshape(-1)
