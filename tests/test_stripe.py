"""Intra-chunk striping and fused batch integrity: algebra, custody, fallbacks.

The striping invariants this file pins down:

* the stripe planner tiles its parent chunk exactly, for every length /
  stripe-count / alignment combination (property tested);
* per-stripe digests fold to the whole-chunk digest via the merge law for
  EVERY partition, not just the planner's even cuts — striping can never
  change what digest a chunk commits under;
* journal custody: a kill mid-stripe leaves only land-AND-verified stripes
  in the journal, and the restart re-moves none of their bytes;
* the fused IntegrityEngine drain reaches the same verdicts as the
  per-chunk path, including catching a single corrupted stripe;
* the hot-path correctness sweep riders: the off-POSIX fallback is safe
  under a concurrent mover pool, BufferPool leases are audit-clean,
  ``fingerprint_many`` validates lengths up front, and ``drain()``'s return
  is authoritative under concurrent submitters.
"""
import os
import threading
import time

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                   # pragma: no cover
    from _hypofallback import given, settings, strategies as st

from repro.core.chunker import Chunk, MiB, plan_chunks, plan_stripes
from repro.core.dataplane import BufferPool, IntegrityEngine, VerifyJob
from repro.core.integrity import fingerprint_bytes, fingerprint_many, merge_all
from repro.core.journal import ChunkJournal
from repro.core.transfer import (
    STRIPE_INDEX_BASE,
    BufferDest,
    BufferSource,
    ChunkedTransfer,
    FileDest,
    FileSource,
)
from repro.tune.controller import ChunkController
from repro.tune.probe import ChunkSample

KiB = 1024


def _payload(seed, nbytes):
    return np.random.default_rng(seed).integers(
        0, 256, nbytes, dtype=np.uint8).tobytes()


# ---------------------------------------------------------------------------
# stripe planning algebra
# ---------------------------------------------------------------------------
@given(
    st.integers(1, 1 << 26),      # chunk length
    st.integers(1, 16),           # requested stripes
    st.integers(1, 4 * MiB),      # stripe_min_bytes
    st.integers(0, 12),           # alignment exponent
)
@settings(max_examples=60, deadline=None)
def test_plan_stripes_tiles_parent_exactly(length, stripes, min_bytes, align_pow):
    align = 1 << align_pow
    chunk = Chunk(index=3, offset=7, length=length, mover=1)
    plan = plan_stripes(chunk, stripes,
                        stripe_min_bytes=min_bytes, alignment=align)
    plan.validate()               # tiling, ordering, positive lengths
    assert 1 <= plan.n_stripes <= stripes
    # interior cut points land on alignment multiples relative to chunk start
    for s in plan.stripes:
        if s.seq > 0:
            assert (s.offset - chunk.offset) % align == 0
    # when striping engaged, every stripe but the tail carries the minimum
    if plan.n_stripes > 1:
        for s in plan.stripes[:-1]:
            assert s.length >= min_bytes


def test_plan_stripes_validates_params():
    c = Chunk(index=0, offset=0, length=MiB, mover=0)
    with pytest.raises(ValueError):
        plan_stripes(c, 0)
    with pytest.raises(ValueError):
        plan_stripes(c, 2, stripe_min_bytes=0)
    with pytest.raises(ValueError):
        plan_stripes(c, 2, alignment=0)


@given(st.binary(min_size=1, max_size=1 << 14), st.integers(1, 8),
       st.integers(1, 512))
@settings(max_examples=40, deadline=None)
def test_stripe_digest_fold_matches_whole_chunk(payload, stripes, min_bytes):
    """The planner's stripes fold to the parent digest via the merge law."""
    chunk = Chunk(index=0, offset=0, length=len(payload), mover=0)
    plan = plan_stripes(chunk, stripes, stripe_min_bytes=min_bytes)
    parts = [fingerprint_bytes(payload[s.offset:s.end]) for s in plan.stripes]
    assert merge_all(parts) == fingerprint_bytes(payload)


@given(st.binary(min_size=0, max_size=4096),
       st.lists(st.integers(0, 4096), max_size=8))
@settings(max_examples=40, deadline=None)
def test_any_partition_folds_to_whole_digest(payload, cuts):
    """Not just the planner's even cuts: EVERY partition folds correctly, so
    a mid-flight stripe-count change can never alter the committed digest."""
    pts = sorted({c % (len(payload) + 1) for c in cuts} | {0, len(payload)})
    pieces = [payload[a:b] for a, b in zip(pts, pts[1:])] or [b""]
    assert merge_all(fingerprint_bytes(p) for p in pieces) == \
        fingerprint_bytes(payload)


# ---------------------------------------------------------------------------
# striped transfers end-to-end
# ---------------------------------------------------------------------------
def test_stripe_engine_param_validation():
    payload = b"x" * 1024
    plan = plan_chunks(1024, 1, chunk_bytes=1024, min_chunk=1, max_chunk=1 << 20)
    with pytest.raises(ValueError):
        ChunkedTransfer(BufferSource(payload), BufferDest(1024), plan, stripes=0)
    with pytest.raises(ValueError):
        ChunkedTransfer(BufferSource(payload), BufferDest(1024), plan,
                        stripes=2, speculative_factor=0.5)
    with pytest.raises(ValueError):
        ChunkedTransfer(BufferSource(payload), BufferDest(1024), plan,
                        stripe_min_bytes=0)


@pytest.mark.parametrize("mode", ["serial", "single_pass", "pipelined"])
@pytest.mark.parametrize("iov", [1, 4])
def test_striped_roundtrip_all_pipeline_modes(mode, iov):
    payload = _payload(11, 3 * MiB)
    plan = plan_chunks(len(payload), 2, chunk_bytes=MiB,
                       min_chunk=1, max_chunk=1 << 30)
    dst = BufferDest(len(payload))
    rep = ChunkedTransfer(
        BufferSource(payload), dst, plan, pipeline=mode,
        integrity_workers=2, stripes=4, stripe_min_bytes=128 * KiB,
        iov_batch=iov,
    ).run()
    assert bytes(dst.buf) == payload
    assert rep.file_digest == fingerprint_bytes(payload)
    assert rep.stripes == 4
    assert rep.striped_chunks == plan.n_chunks    # every chunk was eligible
    # every work item ran in the stripe band, four stripes per plan chunk
    assert all(i >= STRIPE_INDEX_BASE for i in rep.outcomes)
    assert len(rep.outcomes) == 4 * plan.n_chunks


def test_sub_minimum_chunks_are_never_striped():
    payload = _payload(5, 256 * KiB)
    plan = plan_chunks(len(payload), 2, chunk_bytes=64 * KiB,
                       min_chunk=1, max_chunk=1 << 30)
    dst = BufferDest(len(payload))
    rep = ChunkedTransfer(BufferSource(payload), dst, plan,
                          stripes=4, stripe_min_bytes=MiB).run()
    assert bytes(dst.buf) == payload
    assert rep.striped_chunks == 0                # every chunk stayed whole
    assert rep.file_digest == fingerprint_bytes(payload)


class _HostCrash(Exception):
    """Unclassified crash: propagates out of run() like a host death."""


def test_striped_kill_restart_never_removes_journaled(tmp_path):
    """Kill mid-stripe: the journal holds only land-and-verified stripes and
    the restart re-moves zero journaled bytes (the custody rule)."""
    payload = _payload(21, 2 * MiB)
    plan = plan_chunks(len(payload), 1, chunk_bytes=512 * KiB,
                       min_chunk=1, max_chunk=1 << 30)
    jpath = str(tmp_path / "stripe.journal")
    calls = [0]
    survivors = 6                  # stripes journaled before the crash

    def bomb(_chunk, _attempt):
        calls[0] += 1
        if calls[0] > survivors:
            raise _HostCrash("host died mid-stripe")

    dst = BufferDest(len(payload))
    j = ChunkJournal(jpath)
    try:
        with pytest.raises(_HostCrash):
            # serial + 1 mover: stripes land+verify+journal strictly in
            # sequence, so exactly `survivors` records exist at the crash
            ChunkedTransfer(BufferSource(payload), dst, plan, journal=j,
                            fault_injector=bomb, max_retries=0,
                            stripes=4, stripe_min_bytes=64 * KiB).run()
    finally:
        j.close()

    j2 = ChunkJournal(jpath)
    journaled = [(r.offset, r.length) for r in j2.records.values()]
    assert len(journaled) == survivors
    assert all(g >= STRIPE_INDEX_BASE for g in j2.records)   # stripe band

    moved = []
    rep = ChunkedTransfer(
        BufferSource(payload), dst, plan, journal=j2,
        fault_injector=lambda c, _a: moved.append((c.offset, c.length)),
        stripes=4, stripe_min_bytes=64 * KiB,
    ).run()
    j2.close()
    assert bytes(dst.buf) == payload
    assert rep.file_digest == fingerprint_bytes(payload)
    assert rep.skipped_chunks == survivors
    overlaps = [
        m for m in set(moved)
        if any(m[0] < jo + jl and jo < m[0] + m[1] for jo, jl in journaled)
    ]
    assert overlaps == []          # journaled stripes structurally immune
    assert moved                   # ...but the unjournaled rest did move


# ---------------------------------------------------------------------------
# fused batch integrity (engine drain)
# ---------------------------------------------------------------------------
def _engine(record, **kw):
    lock = threading.Lock()

    def ok(job, _lag, _ck):
        with lock:
            record["ok"].append(job.key)

    def bad(job, _actual, _lag):
        with lock:
            record["bad"].append(job.key)

    def err(job, exc):
        with lock:
            record["err"].append((job.key, exc))

    return IntegrityEngine(on_verified=ok, on_corrupt=bad, on_error=err, **kw)


@pytest.mark.parametrize("fuse", [True, False])
def test_fused_drain_catches_corrupted_stripe(fuse):
    """A single corrupted granule is caught by the fused batch dispatch
    exactly like the per-chunk path (verdict parity)."""
    granule, jobs = 4 * KiB, 128
    payload = _payload(31, granule * jobs)
    dst = BufferDest(len(payload))
    dst.write(0, payload)
    dst.buf[17 * granule + granule // 2] ^= 0xFF      # corrupt job 17
    expected = fingerprint_many(
        [payload[i * granule:(i + 1) * granule] for i in range(jobs)])
    record = {"ok": [], "bad": [], "err": []}
    eng = _engine(record, workers=1, fuse=fuse, batch=32)
    try:
        t0 = time.monotonic()
        for i in range(jobs):
            assert eng.submit(VerifyJob(key=i, offset=i * granule,
                                        length=granule, expected=expected[i],
                                        dest=dst, enqueued_s=t0))
        assert eng.drain(timeout=60.0)
    finally:
        eng.close()
    assert record["bad"] == [17]
    assert sorted(record["ok"]) == [i for i in range(jobs) if i != 17]
    assert record["err"] == []
    if fuse:
        # 128 fast submissions against one worker: batching must engage
        assert eng.stats.fused_batches >= 1
        assert eng.stats.fused_jobs > 0


def test_drain_return_is_authoritative_under_concurrent_submit():
    """Satellite: drain() returning True means every job submitted before
    that instant has a verdict — hammered by concurrent submitters and a
    competing drain loop."""
    granule, per_thread, threads_n = 2 * KiB, 100, 3
    payload = _payload(41, granule * per_thread * threads_n)
    dst = BufferDest(len(payload))
    dst.write(0, payload)
    expected = fingerprint_many(
        [payload[i * granule:(i + 1) * granule]
         for i in range(per_thread * threads_n)])
    record = {"ok": [], "bad": [], "err": []}
    eng = _engine(record, workers=2, fuse=True, batch=16)
    stop = threading.Event()

    def submitter(base):
        for i in range(base, base + per_thread):
            assert eng.submit(VerifyJob(key=i, offset=i * granule,
                                        length=granule, expected=expected[i],
                                        dest=dst, enqueued_s=0.0))

    def hammer():
        # racing drains must never deadlock or corrupt pending accounting
        while not stop.is_set():
            eng.drain(timeout=0.002)

    try:
        ts = [threading.Thread(target=submitter, args=(k * per_thread,))
              for k in range(threads_n)]
        hz = threading.Thread(target=hammer)
        hz.start()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        stop.set()
        hz.join()
        assert eng.drain(timeout=60.0)
        # authoritative: every submitted job has exactly one verdict NOW
        assert len(record["ok"]) == per_thread * threads_n
        assert record["bad"] == [] and record["err"] == []
        assert eng.pending == 0
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# accelerator parity: batched checksum kernel
# ---------------------------------------------------------------------------
def test_checksum_many_words_matches_per_stream_and_host():
    jnp = pytest.importorskip("jax.numpy")
    from repro.kernels.checksum import (TILE_BYTES, checksum_many_words,
                                        checksum_words)
    rng = np.random.default_rng(3)
    k, nbytes = 4, 2 * TILE_BYTES
    raw = rng.integers(0, 256, (k, nbytes), dtype=np.uint8)
    words = np.ascontiguousarray(raw).view(np.int32)
    got = np.asarray(checksum_many_words(jnp.asarray(words)))
    assert got.shape[0] == k
    for i in range(k):
        per = np.asarray(checksum_words(jnp.asarray(words[i])))
        assert got[i].tolist() == per.tolist()
        assert tuple(int(v) for v in got[i]) == \
            fingerprint_bytes(raw[i].tobytes()).h


def test_fingerprint_host_rows_divides_bucket_padding_out(monkeypatch):
    """Rows padded to buckets, batches padded to k and rows split into
    pieces all digest bit-equal to the host reference."""
    pytest.importorskip("jax")
    from repro.kernels import ops
    from repro.kernels.checksum import TILE_BYTES
    # two-tile pieces: long rows split, and the 40 short rows need 2 dispatches
    monkeypatch.setattr(ops, "PIECE_BYTES", 2 * TILE_BYTES)
    monkeypatch.setattr(ops, "BUCKETS", (TILE_BYTES, 2 * TILE_BYTES))
    rng = np.random.default_rng(5)
    sizes = [0, 1, 3, 4, TILE_BYTES, TILE_BYTES + 1, 2 * TILE_BYTES,
             5 * TILE_BYTES + 13] + [777] * 40
    rows = [rng.integers(0, 256, n, dtype=np.uint8) for n in sizes]
    assert ops.fingerprint_host_rows(rows) == \
        [fingerprint_bytes(r.tobytes()) for r in rows]


# ---------------------------------------------------------------------------
# satellite: fingerprint_many length validation
# ---------------------------------------------------------------------------
def test_fingerprint_many_expect_equal_rejects_ragged():
    with pytest.raises(ValueError) as ei:
        fingerprint_many([b"aaaa", b"bb", b"cccc"], expect_equal=True)
    msg = str(ei.value)
    assert "items [1] have 2 bytes" in msg        # which items, which lengths
    assert "items [0, 2] have 4 bytes" in msg


def test_fingerprint_many_ragged_falls_back_per_item():
    chunks = [b"", b"a", b"ab", _payload(1, 777), _payload(2, 777), b"a"]
    got = fingerprint_many(chunks)                # no flag: graceful fallback
    assert got == [fingerprint_bytes(c) for c in chunks]


def test_fingerprint_many_equal_lengths_match_per_chunk():
    chunks = [_payload(i, 4096) for i in range(9)]
    assert fingerprint_many(chunks, expect_equal=True) == \
        [fingerprint_bytes(c) for c in chunks]


# ---------------------------------------------------------------------------
# satellite: off-POSIX fallback under a concurrent mover pool
# ---------------------------------------------------------------------------
def test_fallback_file_endpoints_concurrent_movers(tmp_path, monkeypatch):
    """With os.pread/pwrite unavailable, per-thread handles must keep a
    concurrent striped mover pool correct (the shared seek+read handle bug)."""
    import repro.core.transfer as tr
    monkeypatch.setattr(tr, "_HAS_PREAD", False)
    payload = _payload(51, 2 * MiB)
    spath, dpath = str(tmp_path / "src.bin"), str(tmp_path / "dst.bin")
    with open(spath, "wb") as fh:
        fh.write(payload)
    src, dst = FileSource(spath), FileDest(dpath, len(payload))
    assert src._fd is None and dst._fd is None    # fallback path engaged
    try:
        plan = plan_chunks(len(payload), 4, chunk_bytes=128 * KiB,
                           min_chunk=1, max_chunk=1 << 30)
        rep = ChunkedTransfer(src, dst, plan, pipeline="pipelined",
                              integrity_workers=2, stripes=2,
                              stripe_min_bytes=32 * KiB, iov_batch=4).run()
        assert rep.file_digest == fingerprint_bytes(payload)
    finally:
        src.close()
        dst.close()
    with open(dpath, "rb") as fh:
        assert fh.read() == payload
    # close() actually closed every per-thread handle ever vended
    assert src._fallback._all == [] and dst._fallback._all == []


def test_fallback_concurrent_reads_are_isolated(tmp_path, monkeypatch):
    import repro.core.transfer as tr
    monkeypatch.setattr(tr, "_HAS_PREAD", False)
    payload = _payload(52, 512 * KiB)
    spath = str(tmp_path / "s.bin")
    with open(spath, "wb") as fh:
        fh.write(payload)
    src = FileSource(spath)
    errors = []

    def reader(seed):
        rng = np.random.default_rng(seed)
        for _ in range(60):
            off = int(rng.integers(0, len(payload) - 64))
            if src.read(off, 64) != payload[off:off + 64]:
                errors.append(off)
                return

    ts = [threading.Thread(target=reader, args=(i,)) for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    src.close()
    assert errors == []            # no interleaved seek+read corruption


# ---------------------------------------------------------------------------
# satellite: BufferPool lease audit
# ---------------------------------------------------------------------------
def test_buffer_pool_rejects_negative_length():
    pool = BufferPool(1024, capacity=2)
    with pytest.raises(ValueError):
        pool.acquire(-1)


def test_buffer_pool_oversize_one_shot_never_pooled():
    pool = BufferPool(1024, capacity=2)
    buf = pool.acquire(4096)
    assert len(buf.view) == 4096
    assert pool.stats.oversize == 1
    buf.release()
    assert pool._free == []        # one-shot allocation is not retained
    # a normal lease afterwards still cycles through the pool
    b2 = pool.acquire(100)
    b2.release()
    assert len(pool._free) == 1


def test_buffer_pool_double_release_is_noop():
    pool = BufferPool(1024, capacity=4)
    buf = pool.acquire(64)
    buf.release()
    buf.release()                  # idempotent: must not double-insert
    assert len(pool._free) == 1


def test_buffer_pool_exit_is_idempotent_and_exception_safe():
    pool = BufferPool(1024, capacity=4)
    with pool.acquire(64) as buf:
        buf.release()              # early release + __exit__ release: one insert
    assert len(pool._free) == 1
    with pytest.raises(RuntimeError):
        with pool.acquire(64):
            raise RuntimeError("mover died mid-lease")
    assert len(pool._free) == 1    # the lease still came back
    b = pool.acquire(64)
    assert pool.stats.reuses >= 1  # ...and is actually reused
    b.release()


# ---------------------------------------------------------------------------
# tuner: the stripe ladder actuator
# ---------------------------------------------------------------------------
def _sample(length, secs, ck=0.0, lag=0.0):
    return ChunkSample(offset=0, length=length, seconds=secs,
                       attempt_seconds=secs, cksum_seconds=ck, cksum_lag_s=lag)


def test_stripe_ladder_escalates_only_when_pinned_at_max_chunk():
    c = ChunkController(chunk_bytes=MiB, min_chunk=64 * KiB, max_chunk=MiB,
                        epoch_chunks=1, hold_patience=1,
                        stripe_ladder=(1, 2, 4))
    assert c.target_stripes() == 1
    rungs = []
    for _ in range(4):
        c.observe(_sample(MiB, 1.0))
        rungs.append(c.target_stripes())
    # seed epoch, then two pinned grow probes climb the ladder one rung each;
    # the exhausted ladder finally lets the probe turn around (chunk size)
    assert rungs == [1, 2, 4, 4]


def test_stripe_ladder_deescalates_on_multiplicative_decrease():
    c = ChunkController(chunk_bytes=MiB, min_chunk=64 * KiB, max_chunk=MiB,
                        epoch_chunks=1, hold_patience=1,
                        stripe_ladder=(1, 2, 4))
    for _ in range(3):
        c.observe(_sample(MiB, 1.0))
    assert c.target_stripes() == 4
    # rate collapse with checksum NOT dominant: per-byte path degraded —
    # the stripe fan-out may be the cause, shed one rung per MD event
    c.observe(_sample(MiB, 10.0))
    assert c.target_stripes() == 2
    c.observe(_sample(MiB, 100.0))
    assert c.target_stripes() == 1


def test_default_ladder_never_moves():
    c = ChunkController(chunk_bytes=MiB, min_chunk=64 * KiB, max_chunk=MiB,
                        epoch_chunks=1, hold_patience=1)
    for _ in range(6):
        c.observe(_sample(MiB, 1.0))
        assert c.target_stripes() == 1


def test_stripe_ladder_validation():
    for bad in [(), (0,), (2, 1), (1, 1, 2)]:
        with pytest.raises(ValueError):
            ChunkController(chunk_bytes=MiB, stripe_ladder=bad)


def test_tuner_drives_engine_stripe_count():
    """End-to-end: the controller's ladder decision changes the engine's
    live stripe count mid-flight (stripe_replans surfaces it)."""
    payload = _payload(61, 4 * MiB)
    plan = plan_chunks(len(payload), 1, chunk_bytes=256 * KiB,
                       min_chunk=1, max_chunk=1 << 30)
    tuner = ChunkController(chunk_bytes=256 * KiB, min_chunk=256 * KiB,
                            max_chunk=256 * KiB, epoch_chunks=1,
                            hold_patience=1, stripe_ladder=(1, 2))
    dst = BufferDest(len(payload))
    rep = ChunkedTransfer(BufferSource(payload), dst, plan, tuner=tuner,
                          stripes=1, stripe_min_bytes=64 * KiB).run()
    assert bytes(dst.buf) == payload
    assert rep.file_digest == fingerprint_bytes(payload)
    # chunk size is pinned (min==max), so the ladder was the only actuator
    assert rep.stripes == 2
    assert rep.stripe_replans >= 1
    assert rep.striped_chunks > 0


# ---------------------------------------------------------------------------
# service layer: journal-id bands and config validation
# ---------------------------------------------------------------------------
def test_service_stripe_band_routing():
    from repro.service.service import (STRIPE_GID_BASE, STRIPE_ITEM_STRIDE,
                                       TUNE_GID_BASE, _Task)
    from repro.service.task import TaskSpec, TransferItem

    assert STRIPE_GID_BASE > TUNE_GID_BASE       # stripe band sits above
    spec = TaskSpec(task_id="t1", tenant="x", label="",
                    items=(TransferItem("a", "b", 5 * MiB),
                           TransferItem("c", "d", 3 * MiB)))
    t = _Task(spec, 0, chunk_bytes=MiB)
    for item in (0, 1):
        for seq in (0, 1, STRIPE_ITEM_STRIDE - 1):
            g = t.stripe_gidx(item, seq)
            assert g >= STRIPE_GID_BASE
            assert t.item_of_gidx(g) == item
    # a stripe-band record can never be mistaken for a static-plan chunk
    assert not t.static_record_ok(t.stripe_gidx(0, 0), None)


def test_service_config_validates_stripe_params():
    from repro.service.service import ServiceConfig
    with pytest.raises(ValueError):
        ServiceConfig(stripes=0)
    with pytest.raises(ValueError):
        ServiceConfig(stripe_min_bytes=0)


def test_service_striped_transfer_end_to_end(tmp_path):
    from repro.service.service import ServiceConfig, TransferService

    rng = np.random.default_rng(71)
    spath = str(tmp_path / "big.bin")
    payload = rng.integers(0, 256, 1_500_000, dtype=np.uint8).tobytes()
    with open(spath, "wb") as fh:
        fh.write(payload)
    cfg = ServiceConfig(mover_budget=4, max_concurrent_tasks=2,
                        chunk_bytes=512 * KiB, tick_s=0.002,
                        stripes=4, stripe_min_bytes=64 * KiB)
    svc = TransferService(tmp_path / "svc", cfg)
    try:
        [tid] = svc.submit([(spath, spath + ".out")], batch=False)
        status = svc.wait(tid, timeout=60)
        assert status.state == "SUCCEEDED"
        assert status.stripes == 4
        assert status.striped_chunks > 0
        with open(spath + ".out", "rb") as fh:
            assert fh.read() == payload
        [report] = status.item_reports
        assert report.digest_hex == fingerprint_bytes(payload).hexdigest()
    finally:
        svc.close()
