"""Benchmark tests run on the CPU, Pallas kernels interpreted, at tiny sizes.

    python -m pytest bench/tests
"""
import json
import pathlib
import shutil
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# tiny sizes; the control's early stop scaled down with them: one round
CONTROL = {"max_rounds": 1, "rounds_per_heuristic": 1}
TINY = {
    "grid_cut_512": {"sizes": {"height": 16, "width": 128, "pool": 4},
                     "batch": 2, "check_instances": 2,
                     "control": {"solver_kw": CONTROL}},
    "dense_assign_1024": {"sizes": {"n": 16, "pool": 4}, "batch": 2,
                          "check_instances": 4,
                          "control": {"solver_kw": CONTROL}},
}


# Cells whose files are under bench/ but which BENCHMARK.json does not
# measure yet; the copies the tests run hold them too.
QUEUED_CELLS = [
    {"name": "grid_cut_512.batch.mesh4", "config": "grid_cut_512",
     "traffic": "batch.mesh4", "chips": 4, "why": "32 grids a call, 8 a chip"},
    {"name": "dense_assign_1024.batch", "config": "dense_assign_1024",
     "traffic": "batch", "chips": 1, "why": "4 auctions a call"},
]
QUEUED_CONFIGS = [
    {"name": "dense_assign_1024", "source": "DIMACS assignment generator",
     "file": "bench/configs/dense_assign_1024.json", "reduced": [],
     "why": "dense assignment"},
]


def copy_benchmark(dest: pathlib.Path, tiny: bool = True) -> pathlib.Path:
    """A checkout holding ``BENCHMARK.json`` (with ``QUEUED_CELLS``) and
    ``bench/`` (configs made tiny when asked); the program is imported
    from this repository."""
    dest.mkdir(parents=True, exist_ok=True)
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    have = {w["name"] for w in spec["workloads"]}
    spec["workloads"] += [w for w in QUEUED_CELLS if w["name"] not in have]
    have = {c["name"] for c in spec["configs"]}
    spec["configs"] += [c for c in QUEUED_CONFIGS if c["name"] not in have]
    (dest / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))
    shutil.copytree(REPO / "bench", dest / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    if tiny:
        for name, over in TINY.items():
            path = dest / "bench" / "configs" / f"{name}.json"
            cfg = json.loads(path.read_text())
            cfg["sizes"].update(over["sizes"])
            cfg.update({k: v for k, v in over.items() if k != "sizes"})
            path.write_text(json.dumps(cfg, indent=1))
    return dest


@pytest.fixture
def tiny_root(tmp_path):
    return copy_benchmark(tmp_path / "checkout")
