"""BENCHMARK.json against the files the harness finds by name.

Every test runs on two checkouts: this one, and a copy to which a
configuration, a traffic mix, an adapter with its cases, a cell and a
reader are added as files and ``BENCHMARK.json`` entries alone (``DUMMY``).
So every expectation comes from the checkout's ``BENCHMARK.json`` and files,
never from a count of what is there today.
"""
from __future__ import annotations

import copy
import json
import os
import re
import shutil

import pytest

from bench import harness

ROOT = harness.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def one_line(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def reported(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def reader_files(root: str) -> list[str]:
    """The readers a checkout holds, by metric name."""
    return sorted(f[:-3] for f in os.listdir(os.path.join(root, "bench", "metrics"))
                  if f.endswith(".py"))


DUMMY_ADAPTER = '''"""A deployment that moves nothing: a request takes 10 ms."""
import time


class Deployment:
    pool_size = 1

    def __init__(self, config, seed, work, log):
        self.config, self.sent = config, 0

    def start(self):
        return 0

    def send(self, req, timeout):
        time.sleep(0.01)
        self.sent += int(not self.config.get("drop"))

    def counters(self):
        return {"requests_total": {"kind": "counter", "labels": [],
                                   "series": {"": float(self.sent)}}}

    def at_close(self):
        return []

    def window(self, at_close, t0, t_close, log):
        return {"bytes": self.sent, "t_last": t_close, "device_bytes": 0}

    def stop(self):
        return {}, [("wire", "move", 0.0, 1.0, {})]

    def check(self, requests, final, log):
        return {"requests_unfinished": sum(r.t_done is None for r in requests),
                "requests_dropped": len(requests) - self.sent}
'''

DUMMY_CASES = '''"""Runs of the dummy adapter's cells: sound, and its control drops requests."""


def tiny(cell):
    return cell


def _drop(cell):
    cell.config = dict(cell.config, drop=True)


CASES = {"sound": ("sound", None, None, None),
         "control_drop": ("control", _drop, None, "requests_dropped")}
'''

DUMMY_READER = "requests_total.dummy"
DUMMY_CELL = "dummy.closed2"


def _dummy() -> tuple[dict, dict]:
    """The dummy copy's ``BENCHMARK.json`` and the files it adds."""
    bench = copy.deepcopy(BENCH)
    base = harness.load_json(os.path.join(ROOT, bench["configs"][0]["file"]))
    files = {
        "bench/configs/dummy-nothing.json": json.dumps(dict(base, name="dummy-nothing",
                                                            adapter="dummy")),
        "bench/adapters/dummy.py": DUMMY_ADAPTER,
        "bench/cases/dummy.py": DUMMY_CASES,
        "bench/traffic/closed-2clients.json": json.dumps(
            {"loop": "closed", "clients": 2, "files_per_request": 1,
             "draw": "seeded_permutation"}),
        f"bench/metrics/{DUMMY_READER}.py":
            '"""Requests the window sent, by the counter ``requests_total``."""\n\n\n'
            'def read(obs):\n    return obs.counters.get(("requests_total", ""))\n',
    }
    bench["configs"].append(dict(bench["configs"][0], name="dummy-nothing",
                                 file="bench/configs/dummy-nothing.json"))
    bench["workloads"].append({"name": DUMMY_CELL, "config": "dummy-nothing",
                               "traffic": "closed-2clients", "chips": 1,
                               "why": "two closed-loop clients of a deployment that moves nothing"})
    bench["per_layer"].append({"name": DUMMY_READER, "unit": "requests",
                               "better": "higher", "source": "program_counter",
                               "layer": "client API", "moves": "verified_GBps",
                               "workloads": [DUMMY_CELL]})
    files["BENCHMARK.json"] = json.dumps(bench)
    return bench, files


DUMMY, DUMMY_FILES = _dummy()
BENCHES = {"this": BENCH, "files-alone": DUMMY}
READERS = {"this": reader_files(ROOT),
           "files-alone": sorted(reader_files(ROOT) + [DUMMY_READER])}


def _tree(top: str) -> dict:
    out = {}
    for d, _, files in os.walk(top):
        if "__pycache__" not in d:
            for f in files:
                st = os.stat(os.path.join(d, f))
                out[os.path.relpath(os.path.join(d, f), top)] = (st.st_size, st.st_mtime_ns)
    return out


def _state():
    return (_tree(os.path.join(ROOT, "bench")),
            os.stat(os.path.join(ROOT, "BENCHMARK.json")).st_mtime_ns)


@pytest.fixture(scope="module")
def roots(tmp_path_factory) -> dict:
    """Each checkout's root; the dummy copy made once, outside ``bench/``.
    ``"state"`` is this checkout's ``bench/`` before the copy was made."""
    state = _state()
    root = str(tmp_path_factory.mktemp("files-alone") / "checkout")
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    for rel, text in DUMMY_FILES.items():
        with open(os.path.join(root, rel), "w") as fh:
            fh.write(text)
    assert reader_files(root) == READERS["files-alone"]
    return {"this": ROOT, "files-alone": root, "state": state}


@pytest.fixture(params=list(BENCHES))
def checkout(request, roots) -> tuple[dict, str]:
    """``BENCHMARK.json`` and the root of each checkout in turn."""
    return BENCHES[request.param], roots[request.param]


def each(key: str) -> list:
    """``(checkout, entry)`` for every entry under ``key`` of each checkout's
    ``BENCHMARK.json``; this checkout's entries keep their plain names."""
    return [pytest.param(where, x, id=x["name"] if where == "this" else f"{where}-{x['name']}")
            for where, bench in BENCHES.items() for x in bench[key]]


def test_top_level_keys_and_command(checkout):
    bench, root = checkout
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench"]
    assert all(one_line(w) for w in bench["command"])
    assert os.path.isfile(os.path.join(root, bench["command"][1]))
    assert len(json.dumps(bench)) <= 64 * 1024


def test_run_seconds_fits_a_full_check_with_24_cells(checkout):
    bench, root = checkout
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_and_units_use_the_allowed_characters(checkout):
    bench, root = checkout
    names = ([c["name"] for c in bench["configs"]] + [w["name"] for w in bench["workloads"]]
             + [m["name"] for m in bench["end_to_end"] + bench["per_layer"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in bench["configs"]:
        for k in c["reduced"]:
            assert NAME.match(k), k
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for top, _, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            rel = os.path.relpath(os.path.join(top, f), root)
            if "__pycache__" not in rel:
                assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel


@pytest.mark.parametrize("where, cfg", each("configs"))
def test_config_file_is_found_and_states_its_cuts(where, cfg, roots):
    bench, root = BENCHES[where], roots[where]
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert cfg["file"].startswith("bench/") and one_line(cfg["source"]) and one_line(cfg["why"])
    body = harness.load_json(os.path.join(root, cfg["file"]))
    assert body["name"] == cfg["name"]
    assert one_line(body["source"])
    assert sorted(body["reduced"]) == sorted(cfg["reduced"])
    assert body["assumed"] and body["guarantees"]["integrity_escapes"] == 0
    assert any(w["config"] == cfg["name"] for w in bench["workloads"])
    files = [c["file"] for c in bench["configs"]]
    assert files.count(cfg["file"]) == 1

    assert hasattr(harness.load_adapter(body["adapter"], root), "Deployment")
    if body["adapter"] != "transfer":
        return
    from repro.service import ServiceConfig

    sc = ServiceConfig(**body["service"])
    assert sc.integrity and sc.pipeline == "pipelined" and sc.integrity_backend == "pallas"


@pytest.mark.parametrize("where, cfg", each("configs"))
def test_every_adapter_declares_its_sound_run_and_its_control(where, cfg, roots):
    # bench/test_bench_faults.py drives every cell through these runs: a cell
    # whose adapter declares no control could never be shown not correct
    root = roots[where]
    adapter = harness.load_json(os.path.join(root, cfg["file"]))["adapter"]
    cases = harness.load_cases(adapter, root)
    assert callable(cases.tiny)
    kinds = [kind for kind, _change, _fault, _trips in cases.CASES.values()]
    assert set(kinds) <= {"sound", "control", "fault"}
    assert "sound" in kinds and "control" in kinds
    for kind, change, fault, trips in cases.CASES.values():
        assert (trips is None) == (kind == "sound")
        assert change is None or callable(change)
        assert fault is None or callable(fault)


@pytest.mark.parametrize("where, wl", each("workloads"))
def test_cell_resolves_and_reports_what_the_contract_asks(where, wl, roots):
    bench, root = BENCHES[where], roots[where]
    assert set(wl) == {"name", "config", "traffic", "chips", "why"}
    assert wl["chips"] in (1, 4) and one_line(wl["why"])
    cell = harness.resolve_cell(wl["name"], bench, root)
    from bench import dataset

    stream = dataset.RequestStream(cell.traffic, 4, 2**31 + 1)      # the generator reads it
    assert stream.k >= 1
    assert cell.traffic["loop"] == "open" or cell.traffic["clients"] >= 1
    e2e = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert pairs.count((wl["config"], wl["traffic"])) == 1


def test_at_most_half_the_cells_take_four_chips(checkout):
    bench, root = checkout
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_end_to_end_bounds_and_sources(checkout):
    bench, root = checkout
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert all(c in cells for c in m.get("workloads", ()))


@pytest.mark.parametrize("where, m", each("per_layer"))
def test_per_layer_metric_has_a_reader_and_its_moves_target_is_reported(where, m, roots):
    bench, root = BENCHES[where], roots[where]
    e2e = {x["name"]: x for x in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    assert one_line(m["layer"])
    assert callable(harness.load_reader(m["name"], root))
    assert m["moves"] in e2e
    for cell in m.get("workloads", cells):
        assert cell in cells
        assert reported(e2e[m["moves"]], cell), (m["name"], cell)


@pytest.mark.parametrize("where, reader", [
    pytest.param(where, r, id=r if where == "this" else f"{where}-{r}")
    for where, readers in READERS.items() for r in readers])
def test_every_reader_file_is_named_by_a_per_layer_metric(where, reader, roots):
    bench, root = BENCHES[where], roots[where]
    assert reader in {m["name"] for m in bench["per_layer"]}
    assert callable(harness.load_reader(reader, root))


@pytest.mark.parametrize("where, m", each("per_layer"))
def test_every_per_layer_metric_has_a_reader_file(where, m, roots):
    root = roots[where]
    assert m["name"] in reader_files(root)


def test_metrics_of_one_layer_share_its_name(checkout):
    bench, root = checkout
    layers = {}
    for m in bench["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_kernel_files_name_their_events(checkout):
    bench, root = checkout
    kernels = harness.load_kernels(root)
    assert kernels
    for k in kernels:
        path = os.path.join(root, "bench", "kernels", k["name"] + ".json")
        assert os.path.isfile(path)
        assert k["role"] and k["match"] and all(isinstance(p, str) and p for p in k["match"])
        assert one_line(k["source"])
    assert any(k["role"] == "integrity_digest" for k in kernels)


def test_peaks_table_and_a_missing_device_kind_is_an_error():
    v5e = harness.load_peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["bf16_flops_per_s"] == 197e12
    with pytest.raises(LookupError):
        harness.load_peaks("TPU v0 imaginary")
    with pytest.raises(LookupError):
        harness.load_peaks("cpu")


def test_run_without_a_tpu_exits_nonzero_with_no_result(capsys):
    # JAX_PLATFORMS=cpu in the test run: the harness must refuse, not fall back
    rc = harness.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                       "--trace", "0"])
    assert rc != 0
    assert "{" not in capsys.readouterr().out


def test_a_cell_added_as_files_alone_passes_every_contract_test(roots, tmp_path):
    # every test above ran on the dummy copy too (its "files-alone" cases);
    # here the harness runs the new cell from its files, its reader is
    # reported there and only there, its control is not correct, and nothing
    # under this checkout's bench/ was written
    root = roots["files-alone"]
    cell = harness.resolve_cell(DUMMY_CELL, root=root)
    assert [m["name"] for m in cell.per_layer] == [DUMMY_READER]
    assert all(DUMMY_READER not in [m["name"] for m in
               harness.resolve_cell(c, root=root).per_layer] for c in CELLS)
    cases = harness.load_cases("dummy", root)
    outs = {}
    for case, (kind, change, _fault, _trips) in cases.CASES.items():
        run = cases.tiny(harness.resolve_cell(DUMMY_CELL, root=root))
        if change:
            change(run)
        outs[kind] = harness.run_cell(run, 2**31 + 29, 0.3, True,
                                      device={"platform": "cpu", "kind": "cpu", "count": 1},
                                      peaks=None, work=str(tmp_path / case), log=lambda m: None)
    assert outs["sound"]["correct"], outs["sound"]["checks"]
    assert outs["sound"]["metrics"][DUMMY_READER]["value"] >= 1
    assert not outs["control"]["correct"]
    assert outs["control"]["checks"]["requests_dropped"]["value"] >= 1
    assert _state() == roots["state"]
