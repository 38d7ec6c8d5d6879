"""BENCHMARK.json against the files the harness finds by name."""
from __future__ import annotations

import json
import os
import re

import pytest

from bench import harness

ROOT = harness.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
CELLS = [w["name"] for w in BENCH["workloads"]]


def one_line(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def reported(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert all(one_line(w) for w in BENCH["command"])
    assert os.path.isfile(os.path.join(ROOT, BENCH["command"][1]))
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_run_seconds_fits_a_full_check_with_24_cells():
    rs = BENCH["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    runs = 2 + 14 * 24
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_and_units_use_the_allowed_characters():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in BENCH["configs"]:
        for k in c["reduced"]:
            assert NAME.match(k), k
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for root, _, files in os.walk(os.path.join(ROOT, "bench")):
        for f in files:
            rel = os.path.relpath(os.path.join(root, f), ROOT)
            if "__pycache__" not in rel:
                assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_is_found_and_states_its_cuts(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert cfg["file"].startswith("bench/") and one_line(cfg["source"]) and one_line(cfg["why"])
    body = harness.load_json(os.path.join(ROOT, cfg["file"]))
    assert body["name"] == cfg["name"]
    assert one_line(body["source"])
    assert sorted(body["reduced"]) == sorted(cfg["reduced"])
    assert body["assumed"] and body["guarantees"]["integrity_escapes"] == 0
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])
    files = [c["file"] for c in BENCH["configs"]]
    assert files.count(cfg["file"]) == 1

    assert hasattr(harness.load_adapter(body["adapter"]), "Deployment")
    from repro.service import ServiceConfig

    sc = ServiceConfig(**body["service"])
    assert sc.integrity and sc.pipeline == "pipelined" and sc.integrity_backend == "pallas"


@pytest.mark.parametrize("wl", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_and_reports_what_the_contract_asks(wl):
    assert set(wl) == {"name", "config", "traffic", "chips", "why"}
    assert wl["chips"] in (1, 4) and one_line(wl["why"])
    cell = harness.resolve_cell(wl["name"], BENCH)
    from bench import dataset

    stream = dataset.RequestStream(cell.traffic, 4, 2**31 + 1)      # the generator reads it
    assert stream.k >= 1
    assert cell.traffic["loop"] == "open" or cell.traffic["clients"] >= 1
    e2e = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert pairs.count((wl["config"], wl["traffic"])) == 1


def test_at_most_half_the_cells_take_four_chips():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def test_end_to_end_bounds_and_sources():
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert all(c in CELLS for c in m.get("workloads", ()))


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_a_reader_and_its_moves_target_is_reported(m):
    assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    assert one_line(m["layer"])
    assert callable(harness.load_reader(m["name"]))
    assert m["moves"] in E2E
    for cell in m["workloads"]:
        assert cell in CELLS
        assert reported(E2E[m["moves"]], cell), (m["name"], cell)


def test_metrics_of_one_layer_share_its_name():
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_kernel_files_name_their_events():
    kernels = harness.load_kernels()
    assert kernels
    for k in kernels:
        path = os.path.join(ROOT, "bench", "kernels", k["name"] + ".json")
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
