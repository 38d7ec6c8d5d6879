"""The verify split: named spans read as numbers of one window."""
from __future__ import annotations

import copy
import dataclasses

import pytest

from bench import harness, stats, verify_split

GB = int(2e9)                    # device bytes of the synthetic window: 2 GB
CHILDREN = verify_split.INSIDE_VERIFY


def _rec(cat, name, t0, t1, **args):
    return (cat, name, t0, t1, dict({"task": "t", "lane": "verifier0"}, **args))


def _records():
    """Two chunks of one task, each verified by the per-job path, and one
    mover digest that starts before the window."""
    out = [_rec("cksum", "cksum_inline", -1.0, 1.0, offset=0, lane="mover0")]
    for off, enq, a in ((0, 0.5, 2.0), (1, 1.0, 5.0)):
        out += [_rec("cksum_wait", "verify_wait", enq, a, offset=off),
                _rec("cksum", "verify", a, a + 2.0, offset=off),
                _rec("cksum", "verify_readback", a, a + 0.5, offset=off)]
        t = a + 0.5
        for phase, d in zip(verify_split.PHASES, (0.25, 0.5, 0.5, 0.25)):
            out.append(_rec("cksum", f"digest_{phase}", t, t + d, offset=off,
                            bucket=8 << 20, rows=4))
            t += d
    return out


def test_split_of_synthetic_spans():
    res = verify_split.split(_records(), 0.0, 10.0, GB)
    assert res["mover_digest_s_per_GB"] == pytest.approx(0.5)        # clipped to 1 s
    assert res["verify_readback_s_per_GB"] == pytest.approx(0.5)
    assert res["dispatch_stage_s_per_GB"] == pytest.approx(0.25)
    assert res["dispatch_put_s_per_GB"] == pytest.approx(0.5)
    assert res["dispatch_wait_s_per_GB"] == pytest.approx(0.5)
    assert res["dispatch_unpad_s_per_GB"] == pytest.approx(0.25)
    # lags 4.0 - 0.5 and 7.0 - 1.0
    assert res["verify_lag_p50_s"] == pytest.approx(4.75)
    assert res["verify_lag_samples"] == 2
    assert res["verify_s_per_GB"] == pytest.approx(2.0)
    assert res["verify_covered_share"] == pytest.approx(1.0)
    # a window that ends inside the second verify: its lag is not counted
    cut = verify_split.split(_records(), 0.0, 6.0, GB)
    assert cut["verify_lag_samples"] == 1 and cut["verify_lag_p50_s"] == pytest.approx(3.5)
    assert cut["verify_readback_s_per_GB"] == pytest.approx(0.5)


def test_split_is_none_without_spans_or_bytes():
    # a program without the verify path's spans: movers and lag still read
    old = [r for r in _records() if r[1] not in CHILDREN]
    res = verify_split.split(old, 0.0, 10.0, GB)
    for name in verify_split.PER_GB:
        if name != "mover_digest_s_per_GB":
            assert res[name] is None, name
    assert res["verify_covered_share"] is None
    assert res["verify_lag_p50_s"] == pytest.approx(4.75)
    empty = verify_split.split([], 0.0, 10.0, GB)
    assert all(empty[k] is None for k in list(verify_split.PER_GB) + ["verify_lag_p50_s"])
    no_bytes = verify_split.split(_records(), 0.0, 10.0, 0)
    assert all(no_bytes[k] is None for k in verify_split.PER_GB)


def test_child_spans_leave_the_phase_shares_unchanged():
    recs = _records()
    spans = [(c, a, b) for c, _n, a, b, _x in recs]
    bare = [(c, a, b) for c, n, a, b, _x in recs if n not in CHILDREN]
    assert stats.phase_seconds(spans, 0.0, 10.0) == stats.phase_seconds(bare, 0.0, 10.0)


def test_offsets_pair_each_span_with_its_annotation():
    recs = [_rec("cksum", "digest_wait", 10.0 + i, 10.5 + i) for i in range(30)]
    # annotations 3 us late, on a trace clock whose marker sat at 5 s
    starts = [1e12 + (10.0 + i - 5.0) * 1e9 + 3e3 for i in range(30)]
    out = verify_split.offsets(recs, starts, 1e12, 5.0, 0.0, 100.0)
    assert out["spans"] == 30 and out["annotations"] == 30
    for side in ("open", "close"):
        assert out[f"{side}_median_us"] == pytest.approx(3.0, abs=0.01)
        assert out[f"{side}_max_abs_us"] == pytest.approx(3.0, abs=0.01)
    assert verify_split.offsets(recs, [], 1e12, 5.0, 0.0, 100.0) is None


# the verify path's readers and what each reads on ``_records()`` over
# [0, 10] s with 2 GB handed to the device
READERS = {"mover_digest_s_per_GB.dtn": 0.5, "verify_readback_s_per_GB.dtn": 0.5,
           "dispatch_stage_s_per_GB.dtn": 0.25, "dispatch_put_s_per_GB.dtn": 0.5,
           "dispatch_wait_s_per_GB.dtn": 0.5, "dispatch_unpad_s_per_GB.dtn": 0.25,
           "verify_lag_p50_s.dtn": 4.75}
DISPATCHES = "digest_dispatches_total"


def _obs(records, device_bytes=GB, counters=None):
    return harness.Observations(t0=0.0, t1=10.0, device_bytes=device_bytes, device=None,
                                peaks=None, records=records, counters=counters or {})


@pytest.mark.parametrize("name", list(READERS))
def test_verify_path_reader_on_synthetic_records(name):
    read = harness.load_reader(name)
    assert read(_obs(_records())) == pytest.approx(READERS[name])
    assert read(_obs(_records())) == verify_split.split(
        _records(), 0.0, 10.0, GB)[name.removesuffix(".dtn")]
    # nothing to read: no spans at all, or (a rate per GB) no device bytes
    assert read(_obs([])) is None
    if name.endswith("_per_GB.dtn"):
        assert read(_obs(_records(), device_bytes=0)) is None


def test_view_dispatch_share_on_synthetic_counters():
    read = harness.load_reader("view_dispatch_share.dtn")
    view, staged = (DISPATCHES, "view"), (DISPATCHES, "staged")
    assert read(_obs([], counters={view: 3.0, staged: 1.0})) == pytest.approx(75.0)
    assert read(_obs([], counters={view: 5.0})) == pytest.approx(100.0)
    assert read(_obs([], counters={staged: 2.0})) == 0.0
    # no dispatch in the window: nothing to read
    assert read(_obs([], counters={view: 0.0, staged: 0.0})) is None
    assert read(_obs(_records())) is None


def test_a_traced_run_on_the_cpu_reports_every_number(tmp_path):
    cell = harness.resolve_cell("bigfile.closed1")
    cfg = copy.deepcopy(cell.config)
    cfg["service"]["chunk_bytes"] = 1 << 15
    cfg["dataset"]["block_bytes"] = 1 << 12
    cfg["dataset"]["sizes"]["bytes"] = 1 << 17
    cfg["warmup"]["file_bytes"] = [(1 << 15) - 1]
    cell = dataclasses.replace(cell, config=cfg)
    out, res = verify_split.run(
        cell, 2**31 + 23, 1.0, True, device={"platform": "cpu", "kind": "cpu", "count": 1},
        peaks=None, work=str(tmp_path / "work"), log=lambda m: None)
    assert out["correct"], out["checks"]
    for name in verify_split.PER_GB:
        assert res[name] is not None and res[name] > 0, name
    assert res["verify_lag_samples"] >= 1 and res["verify_lag_p50_s"] > 0
    assert 0.5 < res["verify_covered_share"] <= 1.0
    pair = res["digest_wait_vs_annotation"]
    assert pair["annotations"] >= pair["spans"] >= 1
    assert pair["open_max_abs_us"] < 5e3 and pair["close_max_abs_us"] < 5e3
    # the per-layer readers give the split's numbers, on the same run
    for name in READERS:
        assert out["metrics"][name]["value"] == res[name.removesuffix(".dtn")], name
    assert 0 <= out["metrics"]["view_dispatch_share.dtn"]["value"] <= 100
    # the harness is left as it was
    assert harness.load_adapter.__module__ == "bench.harness"
    assert verify_split.devtrace.extract.__module__ == "bench.devtrace"
    assert verify_split.devtrace.reduce.__module__ == "bench.devtrace"


def test_split_without_a_tpu_exits_nonzero_with_no_result(capsys):
    rc = verify_split.main(["--workload", "bigfile.closed1", "--seed", "1",
                            "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert "{" not in capsys.readouterr().out
