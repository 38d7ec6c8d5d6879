"""Fused matmul + operand fingerprint — compute/integrity overlap in one pass.

The paper's Fig. 4 insight is that integrity checking should ride along with
data movement instead of serializing after it. On a TPU the analogous fusion
is at the kernel level: when a transferred tensor is about to be *consumed* by
a matmul (e.g. an FSDP all-gathered weight entering the MXU), the digest can
be computed from the very tiles the MXU is already pulling through VMEM —
zero extra HBM traffic, versus a separate verification pass that re-reads the
whole operand (exactly the "re-read at destination" cost the paper measures
at 773 s for a 500 GB file).

Grid (i, j, k) with k innermost: the f32 accumulator scratch carries the C
block across k; A tiles are digested only on the j == 0 pass, in block-row-
major order — the canonical "blocked" byte order defined by ref.blocked_view.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.integrity import BASES, NBASES, P
from repro.kernels.checksum import resolve_interpret

LANES = 128


def _pow_mod(base: int, exp: int) -> int:
    return pow(int(base), int(exp), P)


@functools.lru_cache(maxsize=None)
def _tables16(bm: int, bk: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Weights for digesting a (bm, bk) bf16 tile as its u16 code units.

    Element m (tile row-major) occupies bytes 2m (lo) and 2m+1 (hi);
    lo-weight = r^(T-1-2m) with T = 2*bm*bk, hi-weight = lo * r^-1.
    """
    tile_elems = bm * bk
    tile_bytes = 2 * tile_elems
    w16 = np.empty((NBASES, bm, bk), np.int32)
    rinv1 = np.empty((NBASES, 1), np.int32)
    rpow = np.empty((NBASES, 1), np.int32)
    for b, r in enumerate(BASES):
        r2inv = _pow_mod(_pow_mod(r, 2), P - 2)
        acc = _pow_mod(r, tile_bytes - 1)
        flat = np.empty(tile_elems, np.int64)
        for m in range(tile_elems):
            flat[m] = acc
            acc = (acc * r2inv) % P
        w16[b] = flat.reshape(bm, bk)
        rinv1[b, 0] = _pow_mod(r, P - 2)
        rpow[b, 0] = _pow_mod(r, tile_bytes)
    return w16, rinv1, rpow


def _mm_digest_kernel(a_ref, b_ref, w16_ref, rinv_ref, rpow_ref,
                      out_ref, dig_ref, acc_ref, *, nk: int):
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((i == 0) & (j == 0) & (k == 0))
    def _init_digest():
        dig_ref[...] = jnp.zeros((1, NBASES), jnp.int32)

    @pl.when(k == 0)
    def _init_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]
    acc_ref[...] += jnp.dot(
        a.astype(jnp.float32), b_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )

    # Digest the A tile on its first (and only) digesting visit: j == 0.
    @pl.when(j == 0)
    def _digest():
        codes = jax.lax.bitcast_convert_type(a, jnp.uint16).astype(jnp.int32)
        lo = jnp.bitwise_and(codes, 255)
        hi = jax.lax.shift_right_logical(codes, 8)
        dig = dig_ref[...]
        new = []
        for bb in range(NBASES):
            w = w16_ref[bb]
            s_lo = jnp.sum(jnp.sum(lo * w, axis=1) % P) % P
            s_hi = jnp.sum(jnp.sum(hi * w, axis=1) % P) % P
            th = (s_lo + s_hi * rinv_ref[bb, 0]) % P
            new.append((dig[0, bb] * rpow_ref[bb, 0] + th) % P)
        dig_ref[...] = jnp.stack(new)[None, :]

    @pl.when(k == nk - 1)
    def _emit():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def matmul_digest(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 128,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """C = A @ B (f32 accumulate) plus digest residues of A's blocked bytes.

    A must be bf16 (the transfer dtype) with shape divisible by (bm, bk);
    B is (K, N) divisible by (bk, bn). Returns (C f32 (M,N), residues (NBASES,)).
    """
    assert a.dtype == jnp.bfloat16, a.dtype
    M, K = a.shape
    K2, N = b.shape
    assert K == K2 and M % bm == 0 and K % bk == 0 and N % bn == 0, (a.shape, b.shape)
    nk = K // bk
    w16, rinv1, rpow = _tables16(bm, bk)
    kernel = functools.partial(_mm_digest_kernel, nk=nk)
    out, dig = pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((NBASES, bm, bk), lambda i, j, k: (0, 0, 0)),
            pl.BlockSpec((NBASES, 1), lambda i, j, k: (0, 0)),
            pl.BlockSpec((NBASES, 1), lambda i, j, k: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((1, NBASES), lambda i, j, k: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), jnp.float32),
            jax.ShapeDtypeStruct((1, NBASES), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=resolve_interpret(interpret),
        name="matmul_digest",
    )(a, b, jnp.asarray(w16), jnp.asarray(rinv1), jnp.asarray(rpow))
    return out, dig[0]
