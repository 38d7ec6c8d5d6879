"""Pallas TPU kernels: compiled on TPU, run in interpret mode on CPU (tests).

  checksum.py      — chunk fingerprint kernel + single-pass checksum-copy
  matmul_digest.py — fused matmul + operand digest (consume-and-verify)
  ops.py           — jit'd public wrappers
  ref.py           — pure-jnp oracles (cross-checked vs host numpy oracle)
"""
from repro.kernels.ops import (
    digest_of,
    fingerprint_and_copy,
    fingerprint_array,
    fingerprint_host_rows,
    matmul_with_digest,
)

__all__ = ["digest_of", "fingerprint_and_copy", "fingerprint_array",
           "fingerprint_host_rows", "matmul_with_digest"]
