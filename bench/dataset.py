"""Seeded datasets and request streams, built from data files.

A configuration's ``dataset`` block fixes a pool of source files: how many,
the law their sizes follow, and the block size of their content. A traffic
file fixes how clients draw requests from that pool. Both are plain
parameters; this module is the one generator that reads them.

Sizes do not depend on the seed: a size law is sampled at the stratified
quantiles ``(i + 0.5) / n``, so every seed moves the same set of sizes. The
seed decides which file gets which size, the bytes in every file, and the
order in which requests draw the files.

Content is a stream of blocks: one seeded random block, XORed per block with
a distinct 64-bit key (splitmix64 of the seed and the block index). No two
blocks are equal, so a chunk landed at the wrong offset never matches, and
the stream is written at memory speed instead of at PRNG speed.
"""
from __future__ import annotations

import dataclasses
import os
import threading

import numpy as np

_M64 = (1 << 64) - 1


def splitmix64(x: int) -> int:
    """The splitmix64 finaliser (a bijection on 64-bit integers)."""
    z = (x + 0x9E3779B97F4A7C15) & _M64
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _M64
    return z ^ (z >> 31)


def file_sizes(spec: dict) -> np.ndarray:
    """Byte sizes of the pool's files, in stratified-quantile order."""
    n = int(spec["files"])
    law = spec["sizes"]
    if law["law"] == "fixed":
        return np.full(n, int(law["bytes"]), np.int64)
    if law["law"] == "truncated_pareto":
        alpha, lo, hi = float(law["alpha"]), float(law["min_bytes"]), float(law["max_bytes"])
        u = (np.arange(n) + 0.5) / n
        x = lo * (1.0 - u * (1.0 - (lo / hi) ** alpha)) ** (-1.0 / alpha)
        return np.clip(np.floor(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown size law {law['law']!r}")


class ContentStream:
    """Seeded byte stream of distinct blocks (see the module docstring)."""

    def __init__(self, seed: int, block_bytes: int, *, stream: int = 0xC0):
        if block_bytes % 8:
            raise ValueError("block_bytes must be a multiple of 8")
        rng = np.random.default_rng([seed, stream])
        self._base = np.frombuffer(rng.bytes(block_bytes), np.uint64)
        self._key0 = int(rng.integers(0, 1 << 63))
        self.block_bytes = block_bytes

    def _key(self, j: int) -> np.uint64:
        return np.uint64(splitmix64(self._key0 + j))

    def read(self, start: int, n: int) -> np.ndarray:
        """Bytes [start, start + n) of the stream, as a new uint8 array
        (thread-safe)."""
        out = np.empty(n, np.uint8)
        bb, pos = self.block_bytes, start
        while pos < start + n:
            j, k = divmod(pos, bb)
            take = min(bb - k, start + n - pos)
            w0, w1 = k // 8, -(-(k + take) // 8)
            words = self._base[w0:w1] ^ self._key(j)
            out[pos - start:pos - start + take] = words.view(np.uint8)[k - 8 * w0:k - 8 * w0 + take]
            pos += take
        return out

    def write_files(self, files: list[tuple[str, int]]) -> None:
        """Write the files back to back from the start of the stream."""
        bb = self.block_bytes
        buf = np.empty_like(self._base)
        cur = memoryview(buf).cast("B")
        j, pos = 0, bb
        for path, size in files:
            with open(path, "wb") as fh:
                left = int(size)
                while left:
                    if pos == bb:
                        np.bitwise_xor(self._base, self._key(j), out=buf)
                        j, pos = j + 1, 0
                    take = min(left, bb - pos)
                    fh.write(cur[pos:pos + take])
                    pos += take
                    left -= take


class Pool:
    """The configuration's source files: (path, bytes), indexed as the
    request stream draws them, laid back to back on one content stream."""

    def __init__(self, spec: dict, seed: int, root: str):
        sizes = file_sizes(spec)
        order = np.random.default_rng([seed, 0xA1]).permutation(sizes.size)
        self.files = [(os.path.join(root, f"f{i:05d}.bin"), int(sizes[order[i]]))
                      for i in range(sizes.size)]
        starts = np.cumsum([0] + [n for _, n in self.files])
        self._start = {p: int(s) for (p, _), s in zip(self.files, starts)}
        self._stream = ContentStream(seed, int(spec["block_bytes"]))
        self.root = root

    def write(self) -> None:
        os.makedirs(self.root, exist_ok=True)
        self._stream.write_files(self.files)

    def expected(self, path: str, offset: int, length: int) -> np.ndarray:
        """The bytes ``path`` holds at [offset, offset + length), made anew
        from the seed (thread-safe; reads no file)."""
        return self._stream.read(self._start[path] + offset, length)


@dataclasses.dataclass
class Request:
    """One client request: the pool indices it draws, its tenant, and, in an
    open loop, its arrival in seconds after the window opens. The deployment
    fills in what it sent; the harness times it on its own clock."""

    number: int
    picks: list[int]
    tenant: str = "default"
    at_s: float | None = None
    items: list = dataclasses.field(default_factory=list)
    task_ids: list[str] = dataclasses.field(default_factory=list)
    t_submit: float = 0.0
    t_done: float | None = None
    error: str | None = None


def stratified(n: int, inverse_cdf, rng: np.random.Generator) -> np.ndarray:
    """``inverse_cdf`` at the quantiles ``(i + 0.5) / n``, in a seeded order:
    every seed gets the same set of values."""
    return rng.permutation(inverse_cdf((np.arange(n) + 0.5) / n))


class RequestStream:
    """Thread-safe source of the requests a traffic file describes.

    Each request draws ``files_per_request`` pool indices, consecutive in a
    seeded sequence of permutations of the pool (``draw``
    ``seeded_permutation``), so over a window every file is drawn about
    equally often whatever the seed. Tenants, where the file names some,
    follow a Zipf law over ``tenants.count`` names.

    ``loop`` is ``closed`` (``clients`` each send a request when their last
    one finished: ``next``) or ``open`` (requests arrive on a schedule
    whatever is in flight: ``schedule``). Open-loop gaps follow
    ``arrivals.law`` ``poisson`` at ``rate_per_s``, each arrival bringing
    ``burst`` requests; the gaps of a window are the law's stratified
    quantiles in a seeded order, so every seed offers the same load.
    """

    def __init__(self, traffic: dict, pool_size: int, seed: int):
        if traffic["loop"] not in ("closed", "open"):
            raise ValueError(f"unsupported loop {traffic['loop']!r}")
        if traffic["draw"] != "seeded_permutation":
            raise ValueError(f"unsupported draw {traffic['draw']!r}")
        self.traffic = traffic
        self.k = int(traffic["files_per_request"])
        self._n = pool_size
        self._seed = seed
        self._rng = np.random.default_rng([seed, 0xD7])
        self._tenant_rng = np.random.default_rng([seed, 0x7E])
        tenants = traffic.get("tenants")
        if tenants:
            if tenants["law"] != "zipf":
                raise ValueError(f"unsupported tenant law {tenants['law']!r}")
            w = 1.0 / np.arange(1, int(tenants["count"]) + 1) ** float(tenants["s"])
            self._tenant_p = w / w.sum()
        else:
            self._tenant_p = None
        self._pending: list[int] = []
        self._lock = threading.Lock()
        self.issued = 0

    def next(self, at_s: float | None = None) -> Request:
        """The next request."""
        with self._lock:
            while len(self._pending) < self.k:
                self._pending.extend(int(i) for i in self._rng.permutation(self._n))
            picks, self._pending = self._pending[:self.k], self._pending[self.k:]
            tenant = "default"
            if self._tenant_p is not None:
                tenant = f"t{int(self._tenant_rng.choice(self._tenant_p.size, p=self._tenant_p)):03d}"
            self.issued += 1
            return Request(self.issued - 1, picks, tenant, at_s)

    def schedule(self, seconds: float) -> list[Request]:
        """Open loop: every request that arrives in a window of ``seconds``,
        in order of arrival."""
        arr = self.traffic["arrivals"]
        if arr["law"] != "poisson":
            raise ValueError(f"unsupported arrival law {arr['law']!r}")
        rate, burst = float(arr["rate_per_s"]), int(arr.get("burst", 1))
        n = max(1, round(rate * seconds))
        gaps = stratified(n, lambda u: -np.log1p(-u) / rate,
                          np.random.default_rng([self._seed, 0xA7]))
        return [self.next(float(t)) for t in np.cumsum(gaps) if t < seconds
                for _ in range(burst)]
