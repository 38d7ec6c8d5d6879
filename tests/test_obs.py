"""Observability stack: tracer invariants, Chrome export round-trip,
metrics snapshot/delta math, histogram bucket properties, attribution
sweep semantics, flight-recorder post-mortem dumps, and the wall-clock
lint (no ``time.time()`` under src/repro outside obs/clock.py)."""
import json
import os
import re
import time

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypofallback import given, settings, strategies as st

from repro.core import BufferDest, BufferSource, ChunkedTransfer, plan_chunks
from repro.obs import (
    CATEGORIES,
    NULL,
    Clock,
    Counter,
    FlightRecorder,
    Gauge,
    Histogram,
    NullTracer,
    Registry,
    Span,
    Tracer,
    attribute,
    by_group,
    delta,
    journal_tail_summary,
    mono_s,
    report,
    wall_s,
)

SRC_ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src", "repro")


def _span(sid, cat, t0, t1, name="x", task="t", lane="", **args):
    return Span(sid, name, cat, t0, t1, task, lane,
                tuple(sorted(args.items())))


# ---------------------------------------------------------------------------
# tracer: span invariants
# ---------------------------------------------------------------------------
def test_span_sids_monotone_and_sorted():
    tr = Tracer()
    sids = [tr.add("a", "wire", 0.0, 1.0, task="t"),
            tr.add("b", "cksum", 0.5, 0.7, task="t"),
            tr.add("c", "queue", 0.0, 0.1, task="u")]
    assert sids == sorted(sids) and len(set(sids)) == 3
    spans = tr.spans()
    assert [s.sid for s in spans] == sorted(s.sid for s in spans)
    assert [s.sid for s in tr.spans(task="t")] == sids[:2]
    assert tr.tasks() == ["t", "u"]


def test_span_t1_clamped_and_args_sorted():
    tr = Tracer()
    tr.add("a", "wire", 5.0, 3.0, task="t", zeta=1, alpha=2)
    (s,) = tr.spans("t")
    assert s.t1 == s.t0 == 5.0 and s.dur == 0.0    # clamp, never negative
    assert s.args == (("alpha", 2), ("zeta", 1))    # deterministic packing
    assert s.arg("zeta") == 1 and s.arg("missing", 9) == 9


def test_unknown_category_rejected():
    tr = Tracer()
    with pytest.raises(ValueError):
        tr.add("a", "disk", 0.0, 1.0, task="t")
    assert "wire" in CATEGORIES and "stall" in CATEGORIES


def test_bounded_buffer_counts_drops():
    tr = Tracer(max_spans_per_task=4)
    for i in range(7):
        tr.add("s", "wire", float(i), float(i) + 0.5, task="t")
    spans = tr.spans("t")
    assert len(spans) == 4 and tr.dropped == 3
    assert spans[0].t0 == 3.0                       # oldest evicted first


def test_mark_and_chunk_chain_ordering():
    tr = Tracer(clock=Clock(lambda: 42.0, virtual=True))
    sid = tr.mark("hello", task="t")
    (m,) = tr.spans("t")
    assert m.sid == sid and m.t0 == m.t1 == 42.0
    # chunk_chain: offset-filtered, (t0, sid)-ordered
    tr.add("move", "wire", 1.0, 2.0, task="t", offset=0)
    tr.add("queue_wait", "queue", 0.0, 1.0, task="t", offset=0)
    tr.add("move", "wire", 1.0, 2.0, task="t", offset=4096)
    chain = tr.chunk_chain("t", 0)
    assert [s.cat for s in chain] == ["queue", "wire"]
    assert all(s.arg("offset") == 0 for s in chain)


def test_null_tracer_is_inert():
    assert isinstance(NULL, NullTracer)
    assert NULL.add("a", "wire", 0.0, 1.0, task="t") == 0
    assert NULL.mark("b", task="t") == 0


# ---------------------------------------------------------------------------
# tracer: Chrome trace_event export round-trip
# ---------------------------------------------------------------------------
def test_export_round_trip(tmp_path):
    tr = Tracer(clock=Clock(lambda: 0.0, virtual=True))
    tr.add("move", "wire", 1.0, 3.0, task="b", lane="mover0", offset=0)
    tr.add("verify", "cksum", 3.0, 3.5, task="b", lane="verify0")
    tr.add("move", "wire", 0.5, 1.0, task="a", lane="mover0")
    path = tr.export(str(tmp_path / "trace.json"))
    doc = json.loads(open(path, encoding="utf-8").read())

    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    ms = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    assert len(xs) == 3 and ms                      # spans + metadata
    # tasks map to pids in sorted-id order starting at 1
    names = {e["args"]["name"]: e["pid"] for e in ms
             if e["name"] == "process_name"}
    assert names == {"a": 1, "b": 2}
    # timestamps are microseconds relative to the earliest span
    assert min(e["ts"] for e in xs) == 0.0
    wire_b = next(e for e in xs if e["pid"] == 2 and e["name"] == "move")
    assert wire_b["ts"] == pytest.approx(500_000.0)  # (1.0 - 0.5) s -> µs
    assert wire_b["dur"] == pytest.approx(2_000_000.0)
    assert wire_b["cat"] == "wire" and "sid" in wire_b["args"]
    assert doc["otherData"]["clock"] == "virtual"
    assert doc["otherData"]["spans"] == 3 and doc["otherData"]["dropped"] == 0


def test_export_deterministic_bytes():
    def build():
        tr = Tracer(clock=Clock(lambda: 0.0, virtual=True))
        tr.add("move", "wire", 1.0, 2.0, task="t", lane="m0", offset=0)
        tr.add("cksum", "cksum", 2.0, 2.5, task="t", lane="v0", offset=0)
        return tr.export_json()
    assert build() == build()


# ---------------------------------------------------------------------------
# clocks
# ---------------------------------------------------------------------------
def test_clock_sources():
    a, b = mono_s(), mono_s()
    assert b >= a
    assert wall_s() > 1.6e9                         # plausibly "now"
    vc = Clock(lambda: 7.5, virtual=True)
    assert vc.now() == 7.5 and vc.virtual
    assert not Tracer().clock.virtual               # default is monotonic


# ---------------------------------------------------------------------------
# metrics: families, snapshot/delta
# ---------------------------------------------------------------------------
def test_counter_and_gauge_semantics():
    reg = Registry()
    c = reg.counter("chunks_total", "c", ("tenant",))
    c.inc(2, tenant="a")
    c.inc(tenant="a")
    assert c.value(tenant="a") == 3.0 and c.value(tenant="b") == 0.0
    with pytest.raises(ValueError):
        c.inc(-1, tenant="a")                       # counters only go up
    with pytest.raises(ValueError):
        c.inc(1, nosuch="a")                        # schema enforced
    g = reg.gauge("active", "g", ())
    g.set(5)
    g.add(-2)
    assert g.value() == 3.0


def test_registry_reregistration_rules():
    reg = Registry()
    c1 = reg.counter("m", "", ("a",))
    assert reg.counter("m", "", ("a",)) is c1       # idempotent
    with pytest.raises(ValueError):
        reg.gauge("m", "", ("a",))                  # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("m", "", ("b",))                # label-schema mismatch


def test_snapshot_delta_math():
    reg = Registry()
    c = reg.counter("ops", "", ("k",))
    g = reg.gauge("level", "", ())
    h = reg.histogram("lat", "", (), scale=1e-3, nbuckets=8)
    c.inc(5, k="x")
    g.set(10)
    h.observe(0.004)
    before = reg.snapshot()
    c.inc(3, k="x")
    c.inc(1, k="y")
    g.set(4)
    h.observe(0.004)
    h.observe(100.0)
    d = delta(before, reg.snapshot())
    assert d["ops"]["series"]["x"] == 3.0           # counters subtract
    assert d["ops"]["series"]["y"] == 1.0           # absent-before from zero
    assert d["level"]["series"][""] == 4.0          # gauges take `after`
    cell = d["lat"]["series"][""]
    assert cell["count"] == 2 and sum(cell["buckets"]) == 2
    assert cell["buckets"][-1] == 1                 # overflow tail
    # snapshot is JSON-ready and immune to later updates
    json.dumps(before)
    h.observe(0.004)
    assert before["lat"]["series"][""]["count"] == 1


# ---------------------------------------------------------------------------
# histogram bucket boundary properties
# ---------------------------------------------------------------------------
@settings(max_examples=60)
@given(st.integers(min_value=1, max_value=10**12))
def test_histogram_bucket_boundaries(n):
    h = Histogram("h", "", (), scale=1e-6, nbuckets=40)
    v = n * 1e-6
    i = h.bucket_index(v)
    assert 0 <= i < h.nbuckets
    # v lies within (upper(i-1), upper(i)] — up to float round-off at the
    # exact power-of-two edges
    assert v <= h.bucket_upper(i) * (1 + 1e-9)
    if 0 < i < h.nbuckets - 1:
        assert v > h.bucket_upper(i - 1) * (1 - 1e-9)
    # edges are monotone; overflow edge is +inf
    uppers = [h.bucket_upper(j) for j in range(h.nbuckets)]
    assert uppers == sorted(uppers) and uppers[-1] == float("inf")


def test_histogram_quantile_is_bucket_edge():
    h = Histogram("h", "", (), scale=1e-6, nbuckets=40)
    assert h.quantile(0.5) == 0.0                   # empty series
    for v in (1e-5, 1e-5, 1e-2):
        h.observe(v)
    q50, q99 = h.quantile(0.5), h.quantile(0.99)
    assert 1e-5 <= q50 < 1e-2 < q99                 # edges bracket the data
    assert q50 == h.bucket_upper(h.bucket_index(1e-5))


# ---------------------------------------------------------------------------
# attribution: saturation-priority event sweep
# ---------------------------------------------------------------------------
def test_attribution_priority_and_exact_sum():
    spans = [
        _span(1, "wire", 0.0, 4.0),
        _span(2, "cksum", 2.0, 6.0),
        _span(3, "stall", 3.0, 5.0),
        _span(4, "queue", 0.0, 8.0),
        _span(5, "task", 0.0, 10.0),                # defines the makespan
    ]
    a = attribute(spans)
    assert a.makespan_s == pytest.approx(10.0)
    # every instant charged to exactly one phase -> shares sum to 1
    assert sum(a.seconds.values()) == pytest.approx(10.0)
    assert sum(a.shares().values()) == pytest.approx(1.0)
    # [0,3) wire beats cksum/queue; [3,5) stall beats all; [5,6) cksum;
    # [6,8) queue; [8,10) idle
    assert a.seconds["wire"] == pytest.approx(3.0)
    assert a.seconds["stall"] == pytest.approx(2.0)
    assert a.seconds["cksum"] == pytest.approx(1.0)
    assert a.seconds["queue"] == pytest.approx(2.0)
    assert a.seconds["idle"] == pytest.approx(2.0)
    assert a.dominant() == "wire"
    js = a.to_json()
    assert js["dominant"] == "wire"
    assert "wire" in a.format("x")                  # ASCII table renders


def test_attribution_cksum_wait_folds_into_cksum():
    spans = [_span(1, "wire", 0.0, 2.0), _span(2, "cksum_wait", 1.0, 2.0)]
    a = attribute(spans)
    # verify-lag wait outranks wire: the second half is checksum-bound
    assert a.seconds["wire"] == pytest.approx(1.0)
    assert a.seconds["cksum"] == pytest.approx(1.0)
    assert "cksum_wait" not in a.seconds


def test_attribution_window_override_and_groups():
    spans = [_span(1, "wire", 0.0, 1.0, hop=0),
             _span(2, "wire", 1.0, 3.0, hop=1),
             _span(3, "stall", 2.5, 3.0, hop=1)]
    a = attribute(spans, t0=0.0, t1=4.0)
    assert a.makespan_s == pytest.approx(4.0)
    assert a.seconds["idle"] == pytest.approx(1.0)
    groups = by_group(spans, "hop")
    assert set(groups) == {"0", "1"}
    assert groups["0"].seconds["wire"] == pytest.approx(1.0)
    assert groups["1"].seconds["stall"] == pytest.approx(0.5)
    rep = report(spans, group_key="hop")
    assert rep["overall"]["dominant"] == "wire"
    assert set(rep["per_hop"]) == {"0", "1"}


# ---------------------------------------------------------------------------
# engine integration: a traced pipelined transfer
# ---------------------------------------------------------------------------
def test_engine_emits_chunk_lifecycle_spans(tmp_path):
    from repro.core import ChunkJournal
    rng = np.random.default_rng(3)
    payload = rng.integers(0, 256, 256 * 1024, dtype=np.uint8).tobytes()
    plan = plan_chunks(len(payload), 2, chunk_bytes=64 * 1024,
                       min_chunk=1, max_chunk=1 << 40, alignment=1)
    tr = Tracer()
    journal = ChunkJournal(str(tmp_path / "eng.journal"))
    rep = ChunkedTransfer(
        BufferSource(payload), BufferDest(len(payload)), plan,
        pipeline="pipelined", integrity_workers=1, journal=journal,
        tracer=tr, task="eng").run()
    journal.close()
    assert rep.total_bytes == len(payload) and rep.pipeline == "pipelined"
    cats = {s.cat for s in tr.spans("eng")}
    assert {"wire", "cksum", "journal", "task"} <= cats
    # each chunk's chain is time-ordered and starts with its wire move
    chain = tr.chunk_chain("eng", 0)
    assert chain and chain == sorted(chain, key=lambda s: (s.t0, s.sid))
    # the attribution of a real run sums to its makespan
    a = attribute(tr.spans("eng"))
    assert sum(a.shares().values()) == pytest.approx(1.0, abs=1e-6)
    assert a.makespan_s > 0


def test_probe_sample_derived_from_span_chain():
    from repro.tune.probe import sample_from_chain
    tr = Tracer()
    tr.add("queue_wait", "queue", 0.0, 1.0, task="t", offset=0)
    tr.add("move", "wire", 1.0, 3.0, task="t", lane="mover1",
           offset=0, attempt=2)
    tr.add("cksum_inline", "cksum", 3.0, 3.5, task="t", offset=0)
    tr.add("refetch", "stall", 3.5, 5.5, task="t", offset=0,
           kind="corruption")
    tr.add("verify_wait", "cksum_wait", 5.5, 6.0, task="t", offset=0)
    s = sample_from_chain(tr.chunk_chain("t", 0), length=4096)
    # the tuner's fault-exclusion rule: stalls are excluded from the
    # congestion signal but kept in end-to-end seconds
    assert s.attempt_seconds == pytest.approx(2.5)  # wire + cksum only
    assert s.seconds == pytest.approx(4.5)          # + stall
    assert s.cksum_seconds == pytest.approx(0.5)
    assert s.cksum_lag_s == pytest.approx(0.5)
    assert s.attempts == 2 and s.refetches == 1 and s.mover == 1
    with pytest.raises(ValueError):
        sample_from_chain([])


def test_probe_leaves_the_verifier_lanes_out_of_the_movers_cksum():
    from repro.tune.probe import sample_from_chain
    tr = Tracer()
    tr.add("move", "wire", 0.0, 1.0, task="t", lane="mover0", offset=0)
    tr.add("cksum_inline", "cksum", 1.0, 1.25, task="t", lane="mover0", offset=0)
    tr.add("verify_wait", "cksum_wait", 1.25, 2.0, task="t", lane="verifier0", offset=0)
    tr.add("verify", "cksum", 2.0, 3.0, task="t", lane="verifier0", offset=0)
    for name, a in (("verify_readback", 2.0), ("digest_stage", 2.25),
                    ("digest_put", 2.5), ("digest_wait", 2.75)):
        tr.add(name, "cksum", a, a + 0.25, task="t", lane="verifier0", offset=0)
    s = sample_from_chain(tr.chunk_chain("t", 0), length=4096)
    # the engine's work is off the mover path: lag-side, not cksum time
    assert s.cksum_seconds == pytest.approx(0.25)
    assert s.attempt_seconds == pytest.approx(1.25)
    assert s.cksum_lag_s == pytest.approx(0.75)


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------
def test_recorder_ring_bounded_and_dirless_dump():
    tr = Tracer()
    tr.add("move", "wire", 0.0, 1.0, task="t", offset=0)
    rec = FlightRecorder(tracer=tr, registry=Registry(), capacity=3)
    for i in range(5):
        rec.record("t", "EV", {"i": i}, t=float(i))
    evs = rec.events("t")
    assert len(evs) == 3 and evs[0]["detail"]["i"] == 2   # oldest dropped
    bundle = rec.dump("t", "corruption", offset=0)
    assert bundle["reason"] == "corruption"
    assert bundle["chunk_offset"] == 0
    assert [s["cat"] for s in bundle["span_chain"]] == ["wire"]
    assert bundle["journal"] == {"present": False}
    assert rec.dumps == ["t:corruption"]


def test_journal_tail_summary_skips_torn_lines(tmp_path):
    p = tmp_path / "journal.ndjson"
    rows = [json.dumps({"chunk_index": i, "offset": i * 10, "length": 10,
                        "status": "verified"}) for i in range(3)]
    p.write_text("\n".join(rows) + "\ngarbage{{{\n")
    s = journal_tail_summary(str(p), n=2)
    assert s["present"] and s["records"] == 3 and s["unreadable_lines"] == 1
    assert len(s["tail"]) == 2 and s["tail"][-1]["chunk_index"] == 2
    assert not journal_tail_summary(str(tmp_path / "nope"))["present"]


def test_fault_campaign_writes_flight_dump(tmp_path):
    """A persistent corruption fault exhausts the re-fetch budget, FAILs
    the task, and the service auto-dumps a post-mortem bundle that names
    the faulted chunk's span chain."""
    from repro.core import IntegrityError
    from repro.service import ServiceConfig, TransferService

    rng = np.random.default_rng(0)
    src = tmp_path / "src.bin"
    src.write_bytes(rng.integers(0, 256, 200_000, dtype=np.uint8).tobytes())
    bad_offset = 2 * 32 * 1024

    def corrupt(task_id, item, chunk, attempt):
        if chunk.offset == bad_offset:
            raise IntegrityError("injected persistent corruption")

    cfg = ServiceConfig(mover_budget=2, max_concurrent_tasks=1,
                        chunk_bytes=32 * 1024, tick_s=0.002,
                        retry_backoff_s=0.001, max_refetches=1)
    svc = TransferService(tmp_path / "svc", cfg, fault_injector=corrupt)
    try:
        [tid] = svc.submit([(str(src), str(src) + ".out")], batch=False)
        stt = svc.wait(tid, timeout=60)
        assert stt.state == "FAILED"
        assert stt.fault is not None and stt.fault.kind == "corruption"
        assert stt.fault.offset == bad_offset
        # the dump is written by the task's worker thread just after the
        # terminal transition that wakes wait() — poll briefly
        flight = tmp_path / "svc" / "flight"
        deadline = time.monotonic() + 10.0
        dumps = []
        while not dumps and time.monotonic() < deadline:
            dumps = sorted(flight.glob("flight_*_corruption.json"))
            time.sleep(0.01)
        assert dumps, "no flight-recorder dump written"
        doc = json.loads(dumps[0].read_text())
        assert doc["task"] == tid and doc["reason"] == "corruption"
        assert doc["chunk_offset"] == bad_offset
        # the bundle carries the faulted chunk's span chain, including the
        # re-fetch stalls that exhausted the budget
        assert doc["span_chain"], "span chain missing from bundle"
        assert all(s["args"].get("offset") == bad_offset
                   for s in doc["span_chain"])
        assert any(s["cat"] == "stall" for s in doc["span_chain"])
        # the event ring saw the FAULT events leading up to the failure
        assert any(e["kind"] == "FAULT" for e in doc["events"])
        assert "metrics" in doc
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# service status: metrics view
# ---------------------------------------------------------------------------
def test_task_status_metrics_view(tmp_path):
    from repro.service import ServiceConfig, TransferService
    rng = np.random.default_rng(1)
    src = tmp_path / "a.bin"
    src.write_bytes(rng.integers(0, 256, 150_000, dtype=np.uint8).tobytes())
    cfg = ServiceConfig(mover_budget=2, max_concurrent_tasks=1,
                        chunk_bytes=32 * 1024, tick_s=0.002,
                        retry_backoff_s=0.001)
    svc = TransferService(tmp_path / "svc", cfg)
    try:
        [tid] = svc.submit([(str(src), str(src) + ".out")], batch=False)
        stt = svc.wait(tid, timeout=60)
        assert stt.state == "SUCCEEDED"
        m = stt.metrics
        assert m["chunks"] >= 5 and m["bytes"] >= 150_000
        assert m["wire_p99_s"] >= m["wire_p50_s"] > 0
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# wall-clock lint: obs/clock.py owns time.time()
# ---------------------------------------------------------------------------
def test_no_wall_clock_outside_obs_clock():
    """Durations must come from obs.clock; time.time() deltas jump under
    NTP slew. The sole permitted call site is obs/clock.py (wall_s)."""
    offenders = []
    for dirpath, _dirs, files in os.walk(SRC_ROOT):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, SRC_ROOT)
            if rel == os.path.join("obs", "clock.py"):
                continue
            text = open(path, encoding="utf-8").read()
            if re.search(r"\btime\.time\(", text):
                offenders.append(rel)
    assert not offenders, f"time.time() outside obs/clock.py: {offenders}"
