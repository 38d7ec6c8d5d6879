"""A whole run without the chip, sound and with the timed path broken.

Every cell is driven through the runs its adapter declares
(``bench/cases/<adapter>.py``), at the size that file gives: the sound
runs are correct; the control and every fault that the cell can have make
``correct`` come out false, each through the check it names. A run that
passes its host memory limit ends itself with a result that is not
correct.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness

DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def _runs() -> list:
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    adapter = {c["name"]: harness.load_json(os.path.join(harness.ROOT, c["file"]))["adapter"]
               for c in bench["configs"]}
    return [pytest.param(w["name"], adapter[w["config"]], case, id=f"{w['name']}-{case}")
            for w in bench["workloads"]
            for case in harness.load_cases(adapter[w["config"]]).CASES]


@pytest.mark.parametrize("name, adapter, case", _runs())
def test_run_is_correct_only_when_sound(name, adapter, case, tmp_path, monkeypatch):
    cases = harness.load_cases(adapter)
    kind, change, fault, trips = cases.CASES[case]
    cell = cases.tiny(harness.resolve_cell(name))
    if change:
        change(cell)
    if fault:
        fault(monkeypatch)
    logs = []
    out = harness.run_cell(cell, 2**31 + 17, 1.0, False, device=DEVICE, peaks=None,
                           work=str(tmp_path / "work"), log=logs.append)
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device", "checks"}
    assert list(out)[-1] == "checks"
    assert out["checks"]["host_memory_over_limit"] == {"value": 0, "limit": 0}
    assert any(m.startswith("host_rss_peak_bytes ") for m in logs)
    assert out["attempted"] >= (2 if case == "landing_altered_verified" else 1)
    if kind == "sound":
        assert out["correct"], out["checks"]
        assert out["failed"] == 0
        assert {m["name"] for m in cell.end_to_end} <= set(out["metrics"])
    else:
        assert not out["correct"]
        assert out["checks"][trips]["value"] > out["checks"][trips]["limit"], out["checks"]


HOG_ADAPTER = '''"""A deployment whose first request holds 64 MB of host memory."""
import time


class Deployment:
    pool_size = 1

    def __init__(self, config, seed, work, log):
        self.held = []

    def start(self):
        return 0

    def send(self, req, timeout):
        if not self.held:
            self.held.append(b"\\x01" * (64 << 20))
        time.sleep(0.05)

    def counters(self):
        return {}
'''

HOG_RUN = """
import sys
sys.path[:0] = [{root!r}, {src!r}]
import jax
from bench import harness

harness.host_memory_limit = lambda: harness.rss_bytes() + (32 << 20)
cell = harness.Cell(name="hog", chips=1, config={{"adapter": "hog"}},
                    traffic={{"loop": "closed", "clients": 1, "files_per_request": 1,
                              "draw": "seeded_permutation"}},
                    end_to_end=[], per_layer=[], root={checkout!r})
harness.run_cell(cell, 2**31 + 41, 60.0, False,
                 device={{"platform": "cpu", "kind": "cpu", "count": 1}}, peaks=None,
                 work={work!r}, log=lambda m: print(m, flush=True))
print("the window ran to its end", flush=True)
"""


def test_a_run_over_its_host_memory_limit_ends_itself_with_a_result(tmp_path):
    checkout = tmp_path / "checkout"
    (checkout / "bench" / "adapters").mkdir(parents=True)
    (checkout / "bench" / "adapters" / "hog.py").write_text(HOG_ADAPTER)
    script = HOG_RUN.format(root=harness.ROOT, src=os.path.join(harness.ROOT, "src"),
                            checkout=str(checkout), work=str(tmp_path / "work"))
    t = time.perf_counter()
    proc = subprocess.run([sys.executable, "-c", script], capture_output=True, text=True,
                          timeout=120, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    took = time.perf_counter() - t
    assert proc.returncode == harness.EXIT_OVER_LIMIT, proc.stderr[-2000:]
    assert took < 50.0                                  # long before the 60 s window ends
    lines = proc.stdout.strip().splitlines()
    out = json.loads(lines[-1])
    assert out["correct"] is False
    assert out["checks"] == {"host_memory_over_limit": {"value": 1, "limit": 0}}
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert out["attempted"] >= 1 and out["device"]["platform"] == "cpu"
    assert any(l.startswith("host memory: rss ") for l in lines)
    assert "the window ran to its end" not in proc.stdout
    assert proc.stderr.strip().splitlines()[-2:] == [
        "check host_memory_over_limit = 1 (limit 0)", "correct = False"]
