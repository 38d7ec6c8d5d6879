"""The device integrity backend through its entry points, in interpret mode.

``integrity_backend="pallas"`` must digest every read-back and deferred-source
byte on the device — fused drains, singleton drains, ragged lengths and jobs
over ``fuse_max_bytes`` alike — and land results bit-equal to the host
reference (``fingerprint_bytes``).
"""
import threading
import time

import numpy as np
import pytest

from repro.core.chunker import MiB, plan_chunks
from repro.core.integrity import fingerprint_bytes
from repro.core.transfer import BufferDest, BufferSource, ChunkedTransfer, FileDest, FileSource
from repro.kernels import checksum as ck
from repro.kernels import ops
from repro.service import BatchConfig, ServiceConfig, TransferService

KiB = 1024
TILE = ck.TILE_BYTES

# (total bytes, chunk bytes): name -> what the engine sees
CASES = {
    "tile_aligned": (4 * TILE, TILE),              # equal tiled rows: fused drain
    "ragged": (3 * TILE + 4099, TILE + 7),         # odd lengths, ragged tail
    "singleton": (TILE + 3, 1 << 20),              # one job: singleton drain
    "over_fuse_max": (9 * MiB + 5, 9 * MiB + 5),   # > fuse_max_bytes (8 MiB)
}


def _payload(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8).tobytes()


@pytest.fixture
def small_pieces(monkeypatch):
    """Quarter-MiB pieces keep the interpreted kernel quick on 9 MiB jobs."""
    monkeypatch.setattr(ops, "PIECE_BYTES", 256 * KiB)
    monkeypatch.setattr(ops, "BUCKETS", tuple(b for b in ops.BUCKETS if b <= 256 * KiB))


@pytest.mark.parametrize("case", list(CASES))
def test_chunked_transfer_pallas_backend_matches_host(case, small_pieces):
    total, chunk = CASES[case]
    payload = _payload(total, total)
    plan = plan_chunks(total, 2, chunk_bytes=chunk, min_chunk=1, max_chunk=1 << 40,
                       alignment=1)
    dst = BufferDest(total)
    rep = ChunkedTransfer(BufferSource(payload), dst, plan, pipeline="pipelined",
                          integrity_backend="pallas").run()
    assert bytes(dst.buf) == payload
    assert rep.file_digest == fingerprint_bytes(payload)
    for out in rep.outcomes.values():
        c = out.chunk
        assert out.digest == fingerprint_bytes(payload[c.offset:c.offset + c.length])
    assert rep.verify_host_bytes == 0
    # read-back and the deferred source digest both ran on the device
    assert rep.verify_device_bytes == 2 * total


@pytest.mark.parametrize("case", list(CASES))
def test_service_pallas_backend_matches_host(tmp_path, case, small_pieces):
    total, chunk = CASES[case]
    items = []
    for i in range(3):
        p = tmp_path / f"f{i}.bin"
        p.write_bytes(_payload(i, total + i))
        items.append((str(p), str(p) + ".out"))
    cfg = ServiceConfig(mover_budget=4, max_concurrent_tasks=2, chunk_bytes=chunk,
                        tick_s=0.002, pipeline="pipelined",
                        integrity_backend="pallas",
                        batch=BatchConfig(direct_bytes=1 << 30))
    svc = TransferService(tmp_path / "svc", cfg)
    try:
        (tid,) = svc.submit(items)
        st = svc.wait(tid, timeout=120)
    finally:
        svc.close()
    assert st.state == "SUCCEEDED", st.error
    for rep in st.item_reports:
        landed = open(rep.dst, "rb").read()
        assert landed == open(rep.src, "rb").read()
        assert rep.digest_hex == fingerprint_bytes(landed).hexdigest()
        for c in rep.chunks:
            part = landed[c["offset"]:c["offset"] + c["length"]]
            assert c["digest"] == fingerprint_bytes(part).hexdigest()
    assert st.verify_host_bytes == 0
    # the service's movers digest the source; the engine the landed bytes
    assert st.verify_device_bytes == st.bytes_total


def test_host_backend_counts_host_bytes(tmp_path):
    src = tmp_path / "s.bin"
    src.write_bytes(_payload(7, 5 * TILE + 1))
    plan = plan_chunks(5 * TILE + 1, 2, chunk_bytes=TILE, min_chunk=1,
                       max_chunk=1 << 40, alignment=1)
    dst = FileDest(tmp_path / "d.bin", plan.total_bytes)
    rep = ChunkedTransfer(FileSource(src), dst, plan, pipeline="pipelined").run()
    assert rep.verify_device_bytes == 0
    # a file source has no stable view: its digest rides the mover, and the
    # engine digests the landed bytes only
    assert rep.verify_host_bytes == plan.total_bytes


def test_integrity_backend_is_validated():
    with pytest.raises(ValueError, match="integrity_backend"):
        ServiceConfig(integrity_backend="gpu")
    with pytest.raises(ValueError, match="pipelined"):
        ServiceConfig(integrity_backend="pallas")          # serial pipeline
    plan = plan_chunks(64, 1, chunk_bytes=64, min_chunk=1, alignment=1)
    src, dst = BufferSource(b"x" * 64), BufferDest(64)
    with pytest.raises(ValueError, match="integrity_backend"):
        ChunkedTransfer(src, dst, plan, pipeline="pipelined", integrity_backend="gpu")
    with pytest.raises(ValueError, match="pipelined"):
        ChunkedTransfer(src, dst, plan, pipeline="single_pass",
                        integrity_backend="pallas")


@pytest.mark.parametrize("platform,want", [("cpu", True), ("tpu", False), ("gpu", None)])
def test_interpret_resolves_from_platform(monkeypatch, platform, want):
    monkeypatch.setattr(ck.jax, "default_backend", lambda: platform)
    assert ck.resolve_interpret(False) is False            # explicit wins
    if want is None:
        with pytest.raises(RuntimeError, match=platform):
            ck.resolve_interpret(None)
    else:
        assert ck.resolve_interpret(None) is want


# ---------------------------------------------------------------------------
# spans of the verify path: read-back and each device dispatch
# ---------------------------------------------------------------------------
DISPATCH = ("digest_stage", "digest_put", "digest_wait", "digest_unpad")
VERIFY_CHILDREN = ("verify_readback",) + DISPATCH


def test_each_dispatch_records_its_four_spans(monkeypatch):
    from repro.obs import NULL, Tracer

    # two-tile pieces: 42 one-tile pieces need two dispatches, 5 two-tile one
    monkeypatch.setattr(ops, "PIECE_BYTES", 2 * TILE)
    monkeypatch.setattr(ops, "BUCKETS", (TILE, 2 * TILE))
    rng = np.random.default_rng(13)
    sizes = [0, 1, TILE, TILE + 1, 2 * TILE, 5 * TILE + 13] + [777] * 40
    rows = [rng.integers(0, 256, n, dtype=np.uint8) for n in sizes]
    tr = Tracer()
    got = ops.fingerprint_host_rows(rows, tracer=tr, task="t", lane="verifier1", offset=7)
    assert got == ops.fingerprint_host_rows(rows, tracer=NULL)
    assert got == [fingerprint_bytes(r.tobytes()) for r in rows]
    spans = tr.spans("t")
    assert len(spans) == 4 * 3
    want = sorted([(TILE, 32), (TILE, 10), (2 * TILE, 5)])
    for name in DISPATCH:
        mine = [s for s in spans if s.name == name]
        assert sorted((s.arg("bucket"), s.arg("rows")) for s in mine) == want
        assert all(s.cat == "cksum" and s.lane == "verifier1" and s.arg("offset") == 7
                   for s in mine)
    # a dispatch's phases follow each other, in the order of the table
    for i in range(0, len(spans), 4):
        four = spans[i:i + 4]
        assert [s.name for s in four] == list(DISPATCH)
        assert all(a.t1 <= b.t0 for a, b in zip(four, four[1:]))
        assert len({(s.arg("bucket"), s.arg("rows")) for s in four}) == 1


# ---------------------------------------------------------------------------
# dispatch path: a full contiguous batch is a view of its row, others staged
# ---------------------------------------------------------------------------
PIECE = 2 * TILE          # shrunk PIECE_BYTES: k = 4 full pieces, k = 8 tiles


@pytest.fixture
def view_pieces(monkeypatch):
    monkeypatch.setattr(ops, "PIECE_BYTES", PIECE)
    monkeypatch.setattr(ops, "BUCKETS", (TILE, PIECE))
    monkeypatch.setattr(ops, "BATCH_BYTES", 4 * PIECE)
    assert ops.batch_rows(PIECE) == 4 and ops.batch_rows(TILE) == 8


def _bytes_row(seed, n):
    return np.random.default_rng(seed).integers(0, 256, n, dtype=np.uint8)


def _odd_row(n):
    buf = bytearray(n + 1)
    buf[1:] = _bytes_row(5, n).tobytes()
    return np.frombuffer(buf, dtype=np.uint8)[1:]


# name -> (rows, staged_bytes of each dispatch in order); a full batch is
# 4 x PIECE, a staged one costs its (k, bucket) stage
VIEW_CASES = {
    "k_full": (lambda: [_bytes_row(1, 4 * PIECE)], [0]),
    "k_plus_one": (lambda: [_bytes_row(2, 5 * PIECE)], [0, 4 * PIECE]),
    "ragged_tail": (lambda: [_bytes_row(3, 4 * PIECE + 777)], [0, 8 * TILE]),
    # pieces 0-1 of row 0 and 0-1 of row 1 share a batch; row 1's 2-5 do not
    "across_rows": (lambda: [_bytes_row(4, 2 * PIECE), _bytes_row(6, 6 * PIECE)],
                    [4 * PIECE, 0]),
    "odd_address": (lambda: [_odd_row(4 * PIECE)], [4 * PIECE]),
    "strided": (lambda: [_bytes_row(7, 8 * PIECE)[::2]], [4 * PIECE]),
    "read_only": (lambda: [np.frombuffer(_bytes_row(8, 4 * PIECE + 1000).tobytes(),
                                         dtype=np.uint8)], [0, 8 * TILE]),
}


def _dispatches(monkeypatch, rows):
    """Digests, the digest_stage spans' staged_bytes, the arrays handed to the
    kernel as (shape, dtype), and the counter's (view, staged) increments."""
    from repro.obs import Tracer

    handed, real = [], ops._checksum_many

    def recording(words, **kw):
        handed.append((words.shape, words.dtype))
        return real(words, **kw)

    monkeypatch.setattr(ops, "_checksum_many", recording)
    before = [ops._M_DISPATCHES.value(path=p) for p in ("view", "staged")]
    tr = Tracer()
    got = ops.fingerprint_host_rows(rows, tracer=tr, task="t")
    monkeypatch.setattr(ops, "_checksum_many", real)
    counts = tuple(ops._M_DISPATCHES.value(path=p) - b
                   for p, b in zip(("view", "staged"), before))
    staged = [s.arg("staged_bytes") for s in tr.spans("t") if s.name == "digest_stage"]
    return got, staged, handed, counts


@pytest.mark.parametrize("case", list(VIEW_CASES))
def test_full_contiguous_batches_go_as_views(case, view_pieces, monkeypatch):
    make, want_staged = VIEW_CASES[case]
    rows = make()
    assert all(r.ctypes.data % 4 == 0 for r in rows) == (case != "odd_address")
    got, staged, handed, counts = _dispatches(monkeypatch, rows)
    assert got == [fingerprint_bytes(r.tobytes()) for r in rows]
    assert staged == want_staged
    assert counts == (want_staged.count(0), len(want_staged) - want_staged.count(0))
    # today's staged result: every batch staged, the same digests and shapes
    monkeypatch.setattr(ops, "_row_view", lambda *a: None)
    all_staged, staged2, handed2, counts2 = _dispatches(monkeypatch, rows)
    assert all_staged == got
    assert all(b > 0 for b in staged2) and counts2 == (0, len(want_staged))
    assert handed == handed2
    assert all(dt == np.int32 and shape[1] * 4 in (TILE, PIECE)
               and shape[0] == ops.batch_rows(shape[1] * 4) for shape, dt in handed)


@pytest.mark.parametrize("where", ["pooled", "one_shot", "aligned_row"])
def test_no_reference_to_the_rows_outlives_the_digest(where, view_pieces, monkeypatch):
    """The rows' buffers can be released and resized as soon as the digest
    returns. ``aligned_row`` sits at a 64-byte boundary, where the CPU
    runtime reads the host buffer in place instead of copying it."""
    from repro.core import dataplane
    from repro.core.dataplane import BufferPool, IntegrityEngine, read_back_fingerprint

    leased = []

    class Recorded(dataplane.ChunkBuffer):
        __slots__ = ()

        def __init__(self, pool, raw, length):
            super().__init__(pool, raw, length)
            leased.append(raw)

    monkeypatch.setattr(dataplane, "ChunkBuffer", Recorded)
    n = 4 * PIECE
    payload = _payload(23, 2 * n)
    pool = BufferPool(n if where == "pooled" else n // 2, capacity=1)
    eng = IntegrityEngine(workers=1, pool=pool, backend="pallas",
                          on_verified=lambda *a: None, on_corrupt=lambda *a: None)
    before = ops._M_DISPATCHES.value(path="view")
    try:
        for i in range(3):
            off = i * n // 2
            want = fingerprint_bytes(payload[off:off + n])
            if where == "aligned_row":
                raw = bytearray(n + 64)
                mv = memoryview(raw)
                row = np.frombuffer(mv, dtype=np.uint8)
                a = -row.ctypes.data % 64
                row[a:a + n] = np.frombuffer(payload[off:off + n], dtype=np.uint8)
                assert ops.fingerprint_host_rows([row[a:a + n]]) == [want]
                del row
                mv.release()
                leased.append(raw)
            else:
                got = read_back_fingerprint(
                    _Landed(payload), off, n, pool=pool,
                    fingerprint=lambda mv: eng._fingerprint(mv, 0, off))
                assert got == want
            for raw in leased:                  # raises BufferError if exported
                raw.append(0)
                raw.pop()
    finally:
        eng.close()
    assert ops._M_DISPATCHES.value(path="view") - before == 3
    assert len(leased) == 3
    assert pool.stats.oversize == (3 if where == "one_shot" else 0)


class _Landed:
    """Landed bytes read back into pooled buffers (no zero-copy view); the
    read of ``hold`` waits for ``release``, so jobs can queue behind it."""

    def __init__(self, payload: bytes, hold: int | None = None):
        self.buf, self.hold = payload, hold
        self.entered, self.release = threading.Event(), threading.Event()

    def read_back_into(self, offset, view):
        if offset == self.hold:
            self.entered.set()
            assert self.release.wait(60)
        view[:] = self.buf[offset:offset + len(view)]
        return len(view)

    def read_back(self, offset, length):
        return self.buf[offset:offset + length]


def _engine_spans(fuse: bool):
    """A pallas engine's spans over five ragged jobs; with ``fuse`` the last
    four queue behind the first and drain as one fused batch."""
    from repro.core.dataplane import BufferPool, IntegrityEngine, VerifyJob
    from repro.obs import Tracer

    lengths = [TILE + 7, TILE - 3, 2 * TILE + 1, 777, TILE]
    offsets = [sum(lengths[:i]) for i in range(len(lengths))]
    payload = _payload(17, sum(lengths))
    dest = _Landed(payload, hold=0 if fuse else None)
    tr, verdicts = Tracer(), []
    eng = IntegrityEngine(workers=1, pool=BufferPool(4 * TILE), tracer=tr, task="t",
                          fuse=fuse, batch=8, backend="pallas",
                          on_verified=lambda job, lag, ck: verdicts.append(job.offset),
                          on_corrupt=lambda job, actual, lag: None)

    def submit(i):
        off, n = offsets[i], lengths[i]
        assert eng.submit(VerifyJob(key=i, offset=off, length=n, dest=dest,
                                    expected=fingerprint_bytes(payload[off:off + n]),
                                    enqueued_s=time.perf_counter()))

    try:
        submit(0)
        if fuse:
            assert dest.entered.wait(60)
        for i in range(1, len(lengths)):
            submit(i)
        dest.release.set()
        assert eng.drain(timeout=120)
    finally:
        eng.close()
    assert sorted(verdicts) == offsets
    assert eng.stats.fused_batches == int(fuse)
    assert eng.stats.fused_jobs == (4 if fuse else 0)
    return tr, offsets


@pytest.mark.parametrize("fuse", [False, True], ids=["per_job", "fused"])
def test_engine_records_one_readback_per_job_inside_its_verify(fuse):
    tr, offsets = _engine_spans(fuse)
    spans = tr.spans("t")
    assert all(s.lane == "verifier0" for s in spans)
    for off in offsets:
        chain = tr.chunk_chain("t", off)
        names = [s.name for s in chain]
        assert names.count("verify_readback") == 1
        (wait,) = [s for s in chain if s.name == "verify_wait"]
        (verify,) = [s for s in chain if s.name == "verify"]
        if fuse and off:
            # a fused job's verify is a slice of its batch's interval and its
            # dispatches carry the batch, not the job (checked below)
            assert not set(DISPATCH) & set(names)
            continue
        assert wait.t1 <= verify.t0
        # the per-job path: its read-back and dispatches, between its
        # verify_wait and the end of its verify
        assert [n for n in names if n in DISPATCH] == list(DISPATCH)
        for s in chain:
            if s.name in VERIFY_CHILDREN:
                assert verify.t0 <= s.t0 <= s.t1 <= verify.t1
    if fuse:
        batch = [s for s in spans if s.name == "verify" and s.arg("fused")]
        assert len(batch) == 4
        lo, hi = min(s.t0 for s in batch), max(s.t1 for s in batch)
        fused = [s for s in spans if s.arg("jobs") is not None]
        # two buckets among the four jobs: two dispatches
        assert [s.name for s in fused] == list(DISPATCH) * 2
        assert all(s.arg("jobs") == 4 and s.arg("offset") is None for s in fused)
        reads = [s for s in spans if s.name == "verify_readback" and s.arg("offset")]
        assert len(reads) == 4
        assert all(lo <= s.t0 <= s.t1 <= hi for s in fused + reads)


@pytest.mark.parametrize("fuse", [False, True], ids=["per_job", "fused"])
def test_child_spans_leave_the_attribution_unchanged(fuse):
    from repro.obs import attribute

    tr, _ = _engine_spans(fuse)
    spans = tr.spans()
    bare = [s for s in spans if s.name not in VERIFY_CHILDREN]
    assert len(bare) < len(spans)
    a, b = attribute(spans), attribute(bare)
    assert a.makespan_s == b.makespan_s
    assert a.seconds == pytest.approx(b.seconds, abs=1e-12)
    assert a.shares() == pytest.approx(b.shares(), abs=1e-12)


def test_host_backend_records_readback_but_no_dispatch():
    from repro.core.dataplane import BufferPool, IntegrityEngine, VerifyJob
    from repro.obs import Tracer

    payload = _payload(19, 3 * TILE)
    tr = Tracer()
    eng = IntegrityEngine(workers=1, pool=BufferPool(TILE), tracer=tr, task="t",
                          fuse=False, on_verified=lambda *a: None,
                          on_corrupt=lambda *a: None)
    try:
        for i in range(3):
            eng.submit(VerifyJob(key=i, offset=i * TILE, length=TILE, dest=_Landed(payload),
                                 expected=fingerprint_bytes(payload[i * TILE:(i + 1) * TILE]),
                                 enqueued_s=0.0))
        assert eng.drain(timeout=60)
    finally:
        eng.close()
    names = [s.name for s in tr.spans("t")]
    assert names.count("verify_readback") == 3 and names.count("verify") == 3
    assert not set(DISPATCH) & set(names)
