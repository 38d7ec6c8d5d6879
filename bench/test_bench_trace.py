"""The trace reduction, on synthetic events and on a small recorded trace."""
from __future__ import annotations

import json
import os

import pytest

from bench import devtrace, harness

KERNELS = harness.load_kernels()
DIGEST = [k["name"] for k in KERNELS if k["role"] == "integrity_digest"]
RECORDED = os.path.join(harness.BENCH, "testdata", "trace_small.json")


def _trace(events, marker_ns=1_000_000.0):
    return {"marker_ns": marker_ns,
            "devices": [{"plane": "/device:TPU:0", "events": events}]}


def test_union_merges_overlaps_and_keeps_order():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)]) == \
        [(0, 3), (5, 8), (10, 11)]
    assert devtrace.union([]) == []


def test_reduce_busy_idle_kernel_and_gaps_on_synthetic_events():
    # marker at host 100.0 s == trace 1 ms; window [100.0, 100.010] s
    ev = [
        ["fusion.1", 1_000_000.0, 2_000_000.0],               # 1-3 ms
        ["chunk_checksum_many.3", 2_000_000.0, 3_000_000.0],  # 2-5 ms, overlaps
        ["copy.2", 8_000_000.0, 4_000_000.0],                 # 8-12 ms, clipped at 11
        ["fusion.1", 20_000_000.0, 1_000_000.0],              # after the window
    ]
    red = devtrace.reduce(_trace(ev), 100.0, 100.0, 100.010, KERNELS)
    assert red.window_s == pytest.approx(0.010)
    assert red.busy_s == pytest.approx(0.004 + 0.003)
    assert red.idle_share == pytest.approx(0.3)
    assert red.kernel_s[DIGEST[0]] == pytest.approx(0.003)
    assert red.op_s == pytest.approx({"fusion.1": 0.002, "chunk_checksum_many.3": 0.003,
                                      "copy.2": 0.003})
    assert [(round(a - 100.0, 6), round(b - 100.0, 6)) for a, b in red.gaps] == \
        [(0.004, 0.007)]
    bd = devtrace.breakdown(red, lambda a, b: "wire")
    assert bd["device_ops"][0][0] in ("chunk_checksum_many.3", "copy.2")
    assert bd["idle_gaps"] == [["wire", pytest.approx(0.003)]]


def test_reduce_of_an_idle_device_is_all_gap():
    red = devtrace.reduce(_trace([]), 5.0, 5.0, 6.0, KERNELS)
    assert red.busy_s == 0.0 and red.idle_share == 1.0
    assert red.gaps == [(5.0, 6.0)]


def test_reduce_on_the_recorded_trace():
    with open(RECORDED) as fh:
        rec = json.load(fh)
    tr, win = rec["trace"], rec["window"]
    red = devtrace.reduce(tr, win["marker_perf_s"], win["t0"], win["t1"], KERNELS)
    # the reduction as recorded on the chip, and its own invariants
    assert red.busy_s == pytest.approx(rec["expect"]["busy_s"], rel=1e-9)
    assert red.kernel_s[DIGEST[0]] == pytest.approx(rec["expect"]["digest_kernel_s"], rel=1e-9)
    assert 0.0 < red.kernel_s[DIGEST[0]] <= red.busy_s <= red.window_s
    gap_s = sum(b - a for a, b in red.gaps)
    assert gap_s + red.busy_s == pytest.approx(red.window_s, rel=1e-6)
    # every digest event of the recorded window is matched by the kernel file
    names = {e[0] for d in tr["devices"] for e in d["events"]}
    assert any(devtrace.matches(k, n) for k in KERNELS for n in names)
