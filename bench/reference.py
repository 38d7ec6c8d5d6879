"""Plain reference for what a transfer lands: bytes and digests.

Independent of the code under test (it imports nothing of ``repro``). The
digest follows the published definition of the service's chunk fingerprint:
over the byte stream b_0..b_{n-1}, for each evaluation point r,

    H_r(b) = sum_k b_k * r^(n-1-k)  mod p,   p = 46337,
    r in (10007, 20011, 31337, 40009),

encoded as the four residues (uint32, little-endian) then the byte length
(uint64, little-endian), in hex. A chunk of c bytes at offset o of an n-byte
file contributes H_r(chunk) * r^(n-o-c) to the file's digest.

Blocks of 32 KiB are reduced with a float64 matrix product: every term
(<= 255 * 46336) and every block sum (<= 3.9e11) is an exact integer below
2^53, so the result is exact.
"""
from __future__ import annotations

import concurrent.futures as cf
import os
import threading
import time

import numpy as np

P = 46337
BASES = (10007, 20011, 31337, 40009)
BLOCK = 1 << 15
SLAB = 64                       # blocks per matrix product (2 MiB)
PIECE = 64 << 20                # bytes per job of the check


def _weights() -> np.ndarray:
    """(BLOCK, 4) float64: row k holds r^(BLOCK-1-k) mod p per base."""
    w = np.empty((BLOCK, len(BASES)), np.int64)
    for b, r in enumerate(BASES):
        acc = 1
        for k in range(BLOCK - 1, -1, -1):
            w[k, b] = acc
            acc = acc * r % P
    return w.astype(np.float64)


_W = _weights()


def _shift(e: int) -> np.ndarray:
    return np.asarray([pow(r, int(e), P) for r in BASES], np.int64)


# FOLD[j] = r^(BLOCK * (SLAB-1-j)): weight of block j of a full slab; the
# last m rows weigh a slab of m blocks. Terms < p^2, sums < SLAB * p^2 < 2^63.
_FOLD = np.stack([_shift(BLOCK * (SLAB - 1 - j)) for j in range(SLAB)])


_scratch = threading.local()


def digest(buf: np.ndarray) -> tuple[int, ...]:
    """The four residues of a 1-D uint8 array (thread-safe)."""
    n = buf.size
    full, rem = divmod(n, BLOCK)
    h = np.zeros(len(BASES), np.int64)
    x = getattr(_scratch, "x", None)
    if x is None:
        x = _scratch.x = np.empty((SLAB, BLOCK), np.float64)
    for s in range(0, full, SLAB):
        m = min(SLAB, full - s)
        np.copyto(x[:m], buf[s * BLOCK:(s + m) * BLOCK].reshape(m, BLOCK), casting="unsafe")
        blk = (x[:m] @ _W).astype(np.int64) % P                  # (m, 4)
        h_slab = (blk * _FOLD[SLAB - m:]).sum(axis=0) % P
        h = (h * _shift(m * BLOCK) + h_slab) % P
    if rem:
        tail = buf[full * BLOCK:].astype(np.float64)
        blk = (tail @ _W[BLOCK - rem:]).astype(np.int64) % P
        h = (h * _shift(rem) + blk) % P
    return tuple(int(v) for v in h)


def hexdigest(h: tuple[int, ...], nbytes: int) -> str:
    raw = b"".join(int(v).to_bytes(4, "little") for v in h)
    return (raw + int(nbytes).to_bytes(8, "little")).hex()


def combine(parts: list[tuple[int, tuple[int, ...], int]], total: int) -> tuple[int, ...]:
    """File residues from (offset, residues, length) of its chunks."""
    h = np.zeros(len(BASES), np.int64)
    for off, ch, n in parts:
        h = (h + np.asarray(ch, np.int64) * _shift(total - off - n)) % P
    return tuple(int(v) for v in h)


def _read(path: str, offset: int, n: int) -> np.ndarray:
    return np.fromfile(path, dtype=np.uint8, count=n, offset=offset)


def _pieces(offset: int, length: int) -> list[tuple[int, int]]:
    """[offset, offset + length) cut into PIECE-byte (offset, length) jobs."""
    return [(o, min(PIECE, offset + length - o)) for o in range(offset, offset + length, PIECE)] \
        or [(offset, 0)]


def check_landed(requests: list, statuses: dict, expected, pool: cf.Executor,
                 on_checked=None) -> dict[str, int]:
    """Numbers compared against their limits (all exact, limit 0).

    ``expected(src, offset, length)`` gives the bytes a source holds, made
    from the seed (never read back from the program's files). For every
    finished request: each task SUCCEEDED; each requested file is reported;
    every landed byte equals the source's; each journaled chunk digest, and
    the file digest they combine to, equal the reference digest of those
    bytes (so, the bytes being equal, of the landed bytes); the engine
    digested no byte on the host and at least every verified byte on the
    device. A request that never finished counts as unfinished.
    ``on_checked(request)`` is called once a request's bytes are compared.
    """
    out = dict(requests_unfinished=0, tasks_not_succeeded=0, files_unreported=0,
               files_differing=0, chunk_digests_wrong=0, file_digests_wrong=0,
               engine_host_bytes=0, device_bytes_short=0)
    reports = []
    for req in requests:
        if req.t_done is None:
            out["requests_unfinished"] += 1
            continue
        sts = [statuses[tid] for tid in req.task_ids]
        out["tasks_not_succeeded"] += sum(st.state != "SUCCEEDED" for st in sts)
        by_dst = {r.dst: r for st in sts for r in st.item_reports}
        mine = []
        for src, dst in req.items:
            rep = by_dst.get(dst)
            if rep is None or rep.src != src:
                out["files_unreported"] += 1
                continue
            mine.append(rep)
        reports.append((req, mine))
        for st in sts:
            out["engine_host_bytes"] += st.verify_host_bytes
            out["device_bytes_short"] += max(0, st.bytes_done - st.verify_device_bytes)

    # reference digest of every distinct source chunk, once per run, in pieces
    # folded by the merge law
    t0 = time.perf_counter()
    keys = sorted({(r.src, c["offset"], c["length"]) for _, reps in reports
                   for r in reps for c in r.chunks})
    jobs = sorted({(src, o, n) for src, off, length in keys for o, n in _pieces(off, length)})
    piece = dict(zip(jobs, pool.map(lambda k: digest(expected(*k)), jobs)))
    ref = {(src, off, length): combine([(o - off, piece[(src, o, n)], n)
                                        for o, n in _pieces(off, length)], length)
           for src, off, length in keys}
    for _, reps in reports:
        for rep in reps:
            parts = []
            for c in rep.chunks:
                h = ref[(rep.src, c["offset"], c["length"])]
                parts.append((c["offset"], h, c["length"]))
                out["chunk_digests_wrong"] += c["digest"] != hexdigest(h, c["length"])
            covered = sum(c["length"] for c in rep.chunks) == rep.nbytes
            whole = hexdigest(combine(parts, rep.nbytes), rep.nbytes)
            out["file_digests_wrong"] += rep.digest_hex != whole or not covered

    # every landed byte of every finished request, request by request
    t1 = time.perf_counter()

    def same_bytes(job) -> bool:
        rep, o, n = job
        got = _read(rep.dst, o, n)
        return got.size == n and bool(np.array_equal(got, expected(rep.src, o, n)))

    nbytes = 0
    for req, reps in reports:
        for rep in reps:
            size = os.path.getsize(rep.dst) if os.path.exists(rep.dst) else -1
            same = size == rep.nbytes and all(pool.map(
                same_bytes, [(rep, o, n) for o, n in _pieces(0, rep.nbytes)]))
            out["files_differing"] += not same
            nbytes += rep.nbytes
        if on_checked is not None:
            on_checked(req)
    out["digest_s"], out["compare_s"] = t1 - t0, time.perf_counter() - t1
    out["files_checked"] = sum(len(reps) for _, reps in reports)
    out["bytes_compared"] = nbytes
    return out
