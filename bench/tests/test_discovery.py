"""A new configuration, traffic mix and per-layer metric are new files and
new entries only: the harness finds them by name, with no edit."""
import hashlib
import json
import time

from bench import harness
from bench import trace as trace_mod
from test_rehearsal import CHIP_TRACE

GEN = '''"""Grid cuts whose terminal arcs all sit on the first and last columns."""
import numpy as np


def pool(rng, params):
    out = []
    for _ in range(params["pool"]):
        h, w = params["height"], params["width"]
        cap = rng.integers(0, 6, size=(4, h, w)).astype(np.float32)
        cap[0, 0, :] = cap[1, -1, :] = cap[2, :, 0] = cap[3, :, -1] = 0
        cs = np.zeros((h, w), np.float32)
        ct = np.zeros((h, w), np.float32)
        cs[:, 0] = 9
        ct[:, -1] = 9
        out.append((cap, cs, ct))
    return out
'''

METRIC = '''"""Answers the window returned (``inst``)."""


def read(record):
    return len(record["rounds"])
'''


def digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_files_only(tiny_root, monkeypatch):
    before = digest(tiny_root)
    bench = tiny_root / "bench"
    (bench / "gen" / "edge_terminals.py").write_text(GEN)
    cfg = json.loads((bench / "configs" / "grid_cut_512.json").read_text())
    cfg.update(name="edge_cut_small", generator="edge_terminals",
               sizes={"height": 8, "width": 128, "pool": 3})
    (bench / "configs" / "edge_cut_small.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "pairs.json").write_text(json.dumps(
        {"loop": "closed", "shard": False}))
    (bench / "metrics" / "answers.seen.py").write_text(METRIC)
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "edge_cut_small", "source": "test",
                            "file": "bench/configs/edge_cut_small.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "edge_cut_small.pairs",
                              "config": "edge_cut_small", "traffic": "pairs",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "answers.seen", "unit": "inst",
                              "better": "higher", "source": "program_counter",
                              "layer": "solver loop", "moves": "inst_per_s",
                              "workloads": ["edge_cut_small.pairs"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    after = digest(tiny_root)
    changed = {p for p in before if before[p] != after[p]}
    assert changed == {tiny_root.joinpath("BENCHMARK.json")
                       .relative_to(tiny_root)}

    cell = harness.load_cell(tiny_root, "edge_cut_small.pairs")
    res = harness.run_cell(cell, 5, 1.0, False, time.monotonic(),
                           log=lambda *a: None)
    assert res["correct"] is True and res["attempted"] > 0
    assert set(res["metrics"]) == {"inst_per_s", "setup_s"}

    monkeypatch.setattr(trace_mod, "find_xplane", lambda d: str(CHIP_TRACE))
    res = harness.run_cell(cell, 5, 1.0, True, time.monotonic(),
                           log=lambda *a: None)
    assert res["correct"] is True
    assert res["metrics"] == {"answers.seen": {
        "value": float(res["attempted"]), "unit": "inst"}}
