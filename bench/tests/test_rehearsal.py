"""CPU rehearsal: every cell's loop, check and metric readers, tiny sizes.

The command refuses the CPU, so these tests call the harness's functions.
A traced run reads a small trace recorded on the chip
(``data/chip_small.xplane.pb``) in place of the CPU's own, which has no
device plane.
"""
import json
import os
import pathlib
import subprocess
import sys
import time

import pytest

from bench import harness
from bench import trace as trace_mod

DATA = pathlib.Path(__file__).parent / "data"
CHIP_TRACE = DATA / "chip_small.xplane.pb"
SEED = 2 ** 31 + 12345
REPO = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
ONE_CHIP = [w["name"] for w in SPEC["workloads"] if w["chips"] == 1]


def use_chip_trace(monkeypatch):
    monkeypatch.setattr(trace_mod, "find_xplane", lambda d: str(CHIP_TRACE))


def run(root, workload, trace, **kw):
    cell = harness.load_cell(root, workload)
    return cell, harness.run_cell(cell, SEED, 1.0, trace, time.monotonic(),
                                  log=lambda *a: None, **kw)


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_untraced_run_reports_the_cells_end_to_end_metrics(tiny_root,
                                                           workload):
    cell, res = run(tiny_root, workload, False)
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_traced_run_reports_the_cells_per_layer_metrics(tiny_root, workload,
                                                        monkeypatch):
    use_chip_trace(monkeypatch)
    cell, res = run(tiny_root, workload, True)
    assert res["correct"] is True
    assert set(res["metrics"]) == {m["name"] for m in cell.per_layer}
    for name, m in res["metrics"].items():
        assert m["value"] >= 0, name
        if name.startswith("device_idle"):
            assert m["value"] <= 100, name
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    bd = res["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_cpu_is_refused(tmp_path):
    """The command itself prints no result without a TPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "grid_cut_512.batch",
         "--seed", "0", "--seconds", "10", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert '"metrics"' not in p.stdout
