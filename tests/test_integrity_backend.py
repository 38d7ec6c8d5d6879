"""The device integrity backend through its entry points, in interpret mode.

``integrity_backend="pallas"`` must digest every read-back and deferred-source
byte on the device — fused drains, singleton drains, ragged lengths and jobs
over ``fuse_max_bytes`` alike — and land results bit-equal to the host
reference (``fingerprint_bytes``).
"""
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
