"""Generators, the reference digest and the metric arithmetic."""
from __future__ import annotations

import concurrent.futures as cf
import os
import types

import numpy as np
import pytest

from bench import dataset, devtrace, harness, reference, stats

BIG = harness.resolve_cell("bigfile.closed1")
# a heavy-tailed many-file pool, as the generator reads it from a configuration
PARETO = {"files": 2000, "block_bytes": 8 << 20,
          "sizes": {"law": "truncated_pareto", "alpha": 0.4, "min_bytes": 4096,
                    "max_bytes": 64 << 20}}
CLOSED8 = {"loop": "closed", "clients": 8, "files_per_request": 8,
           "draw": "seeded_permutation"}


def _read_all(pool):
    return [open(p, "rb").read() for p, _ in pool.files]


def _pool(spec, seed, root):
    pool = dataset.Pool(spec, seed, root)
    pool.write()
    return pool


def test_pool_is_deterministic_by_seed_and_sizes_do_not_depend_on_it(tmp_path):
    spec = dict(PARETO, files=60, block_bytes=1 << 12)
    spec["sizes"] = dict(spec["sizes"], max_bytes=1 << 16)
    a = _pool(spec, 2**31 + 5, str(tmp_path / "a"))
    b = _pool(spec, 2**31 + 5, str(tmp_path / "b"))
    c = _pool(spec, 7, str(tmp_path / "c"))
    assert [n for _, n in a.files] == [n for _, n in b.files]
    assert _read_all(a) == _read_all(b)
    assert _read_all(a) != _read_all(c)
    assert sorted(n for _, n in a.files) == sorted(n for _, n in c.files)


def test_expected_bytes_are_the_written_bytes(tmp_path):
    spec = dict(PARETO, files=30, block_bytes=1 << 12)
    spec["sizes"] = dict(spec["sizes"], max_bytes=1 << 15)
    pool = _pool(spec, 2**31 + 9, str(tmp_path))
    for (path, n), data in zip(pool.files, _read_all(pool)):
        assert pool.expected(path, 0, n).tobytes() == data
        for a in (1, 7, n // 3):
            assert pool.expected(path, a, n - a - 5).tobytes() == data[a:n - 5]


def test_manyfile_sizes_are_heavy_tailed_and_ragged():
    sizes = dataset.file_sizes(PARETO)
    assert sizes.size == 2000
    assert sizes.min() >= 4096 and sizes.max() <= 64 << 20
    assert (sizes % 4 != 0).sum() > sizes.size // 2
    assert 0.02 < (sizes > 8 << 20).mean() < 0.05
    assert 16_000 < np.median(sizes) < 32_000


def test_no_two_bigfile_chunks_are_equal(tmp_path):
    cfg = BIG.config
    chunk, block = cfg["service"]["chunk_bytes"], cfg["dataset"]["block_bytes"]
    n_blocks = cfg["dataset"]["sizes"]["bytes"] // block
    assert chunk % block == 0 and cfg["dataset"]["sizes"]["bytes"] % chunk == 0
    # every block carries its own key, and a chunk is whole blocks
    cs = dataset.ContentStream(2**31 + 11, block)
    keys = {int(cs._key(j)) for j in range(n_blocks)}
    assert len(keys) == n_blocks
    assert dataset.splitmix64(0) == 0xE220A8397B1DCDAF      # the published first output
    # the same construction, small: every chunk of the file differs
    small = dataset.ContentStream(2**31 + 11, 1 << 12)
    path = str(tmp_path / "f.bin")
    small.write_files([(path, 1 << 16)])
    data = open(path, "rb").read()
    chunks = {data[i:i + (1 << 14)] for i in range(0, len(data), 1 << 14)}
    assert len(chunks) == 4
    blocks = {data[i:i + (1 << 12)] for i in range(0, len(data), 1 << 12)}
    assert len(blocks) == 16


def test_request_stream_is_deterministic_and_covers_the_pool():
    def draw(seed, n):
        s = dataset.RequestStream(CLOSED8, 2000, seed)
        return [(r.number, r.picks) for r in (s.next() for _ in range(n))]

    a, b = draw(2**31 + 3, 250), draw(2**31 + 3, 250)
    assert a == b and a != draw(4, 250)
    files = [i for _, idx in a for i in idx]
    assert sorted(files) == list(range(2000))          # one epoch = one permutation
    assert [n for n, _ in a] == list(range(250))
    one = dataset.RequestStream(BIG.traffic, 1, 9)
    assert [one.next().picks for _ in range(3)] == [[0], [0], [0]]


def test_open_loop_offers_the_same_arrivals_to_every_seed():
    storm = dict(CLOSED8, loop="open", arrivals={"law": "poisson", "rate_per_s": 20.0,
                                                 "burst": 2},
                 tenants={"law": "zipf", "count": 4, "s": 1.2})
    a = dataset.RequestStream(storm, 2000, 2**31 + 21).schedule(30.0)
    b = dataset.RequestStream(storm, 2000, 2**31 + 21).schedule(30.0)
    c = dataset.RequestStream(storm, 2000, 5).schedule(30.0)
    assert [(r.at_s, r.picks, r.tenant) for r in a] == [(r.at_s, r.picks, r.tenant) for r in b]
    assert [r.at_s for r in a] != [r.at_s for r in c]
    gaps = lambda rs: sorted(np.round(np.diff([0.0] + [r.at_s for r in rs[::2]]), 9))
    assert gaps(a) == gaps(c)                          # the same gaps, in another order
    assert all(0 <= r.at_s < 30.0 for r in a) and [r.number for r in a] == list(range(len(a)))
    assert 500 < len(a) <= 1200 and a[0].at_s == a[1].at_s       # bursts of two
    tenants = [r.tenant for r in a]
    assert tenants.count("t000") > tenants.count("t003") > 0
    with pytest.raises(ValueError):
        dataset.RequestStream(dict(CLOSED8, loop="sideways"), 10, 1)


def _status(tid, rep, nbytes, **kw):
    base = dict(task_id=tid, state="SUCCEEDED", item_reports=[rep], bytes_done=nbytes,
                verify_device_bytes=nbytes, verify_host_bytes=0)
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_check_compares_every_landed_byte_of_every_request(tmp_path):
    spec = {"files": 1, "block_bytes": 1 << 12, "sizes": {"law": "fixed", "bytes": 3 << 14}}
    pool = _pool(spec, 2**31 + 33, str(tmp_path / "src"))
    (src, n), = pool.files
    chunk = 1 << 14
    reqs, statuses = [], {}
    for i in range(5):
        dst = str(tmp_path / f"d{i}.bin")
        open(dst, "wb").write(open(src, "rb").read())
        chunks = []
        for off in range(0, n, chunk):
            h = reference.digest(pool.expected(src, off, chunk))
            chunks.append({"offset": off, "length": chunk, "digest": reference.hexdigest(h, chunk)})
        whole = reference.hexdigest(reference.digest(pool.expected(src, 0, n)), n)
        rep = types.SimpleNamespace(src=src, dst=dst, nbytes=n, chunks=chunks, digest_hex=whole)
        statuses[f"t{i}"] = _status(f"t{i}", rep, n)
        reqs.append(dataset.Request(i, [0], items=[(src, dst)], task_ids=[f"t{i}"], t_done=1.0))
    seen = []

    def run():
        with cf.ThreadPoolExecutor(3) as ex:
            return reference.check_landed(reqs, statuses, pool.expected, ex, seen.append)

    out = run()
    assert all(out[k] == 0 for k in ("files_differing", "chunk_digests_wrong",
                                      "file_digests_wrong", "files_unreported"))
    assert out["bytes_compared"] == 5 * n and len(seen) == 5
    # one byte of the last request's landing flipped: found, whatever the seed
    data = bytearray(open(reqs[4].items[0][1], "rb").read())
    data[n - 1] ^= 1
    open(reqs[4].items[0][1], "wb").write(bytes(data))
    assert run()["files_differing"] == 1
    # a journaled digest that is not the source's
    statuses["t2"].item_reports[0].chunks[1]["digest"] = "00" * 24
    out = run()
    assert out["chunk_digests_wrong"] == 1 and out["file_digests_wrong"] == 0
    statuses["t3"].item_reports[0].digest_hex = "00" * 24
    assert run()["file_digests_wrong"] == 1
    reqs.append(dataset.Request(5, [0]))               # never finished
    assert run()["requests_unfinished"] == 1


def _horner(data: bytes) -> tuple[int, ...]:
    out = []
    for r in reference.BASES:
        h = 0
        for byte in data:
            h = (h * r + byte) % reference.P
        out.append(h)
    return tuple(out)


@pytest.mark.parametrize("n", [0, 1, 3, 4097, reference.BLOCK, reference.BLOCK + 5])
def test_reference_digest_is_the_published_definition(n):
    data = np.random.default_rng(n).integers(0, 256, n, dtype=np.uint8)
    assert reference.digest(data) == _horner(data.tobytes())


def test_reference_agrees_with_the_service_digest_and_its_merge_law():
    from repro.core.integrity import fingerprint_bytes

    data = np.random.default_rng(1).integers(0, 256, 3 * (1 << 20) + 77, dtype=np.uint8)
    h = reference.digest(data)
    assert reference.hexdigest(h, data.size) == fingerprint_bytes(data).hexdigest()
    cuts = [0, 1 << 20, (2 << 20) + 5, data.size]
    parts = [(a, reference.digest(data[a:b]), b - a) for a, b in zip(cuts, cuts[1:])]
    assert reference.combine(parts, data.size) == h


def test_rate_from_bytes_done_at_the_close():
    # 52 chunks of 256 MiB, the last committed 50.2 s after the open
    assert stats.rate(52 << 28, 10.0, 60.2) == pytest.approx((52 << 28) / 50.2 / 1e9)
    assert stats.rate(0, 10.0, 10.0) == 0.0


def test_phase_seconds_matches_the_service_attribution():
    from repro.obs.attr import attribute
    from repro.obs.trace import Span

    rng = np.random.default_rng(3)
    cats = ("wire", "cksum", "cksum_wait", "journal", "queue", "stall", "task")
    spans = []
    for i in range(400):
        a = float(rng.uniform(0, 10))
        spans.append(Span(i, "s", cats[i % len(cats)], a, a + float(rng.exponential(0.2))))
    mine = stats.phase_seconds([(s.cat, s.t0, s.t1) for s in spans], 1.0, 9.0)
    theirs = attribute(spans, t0=1.0, t1=9.0).seconds
    assert mine == pytest.approx(theirs)
    assert sum(mine.values()) == pytest.approx(8.0)


def _obs(device=None, **kw):
    spans = [("wire", 0.0, 4.0), ("cksum", 3.0, 6.0), ("journal", 6.0, 7.0)]
    base = dict(t0=0.0, t1=10.0, spans=spans,
                device_bytes=int(8.19e9), device=device, peaks={"hbm_bytes_per_s": 819e9})
    base.update(kw)
    return harness.Observations(**base)


def test_readers_on_synthetic_observations():
    red = devtrace.DeviceReduction(window_s=10.0, busy_s=0.5, op_s={},
                                   kernel_s={"checksum_many_words": 0.4},
                                   roles={"checksum_many_words": "integrity_digest"},
                                   gaps=[])
    obs = _obs(red)
    read = {n: harness.load_reader(n) for n in (
        "wire_share.dtn", "cksum_share.dtn", "device_idle_share.dtn", "digest_roofline.dtn")}
    assert read["wire_share.dtn"](obs) == pytest.approx(40.0)
    assert read["cksum_share.dtn"](obs) == pytest.approx(20.0)
    assert read["device_idle_share.dtn"](obs) == pytest.approx(95.0)
    # 8.19 GB at 819 GB/s is 10 ms of HBM time, in 0.4 s of kernel time
    assert read["digest_roofline.dtn"](obs) == pytest.approx(2.5)
    # nothing to read: no trace, or no kernel time -> left out, never 0
    assert read["device_idle_share.dtn"](_obs()) is None
    assert read["digest_roofline.dtn"](_obs()) is None
    red.kernel_s["checksum_many_words"] = 0.0
    assert read["digest_roofline.dtn"](_obs(red)) is None


def test_every_reader_is_importable_by_file():
    names = [f[:-3] for f in os.listdir(os.path.join(harness.BENCH, "metrics"))
             if f.endswith(".py")]
    assert len(names) == 4
    for n in names:
        assert callable(harness.load_reader(n))
