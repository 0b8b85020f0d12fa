"""``BENCHMARK.json`` keeps to the benchmark's contract, and every file it
names exists."""
import json
import pathlib
import re

REPO = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and not re.search(
        r"[\n\t]", s)


def test_top_level():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert len((REPO / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    # a full check of 24 cells fits in its time
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs():
    used = {w["config"] for w in SPEC["workloads"]}
    files = set()
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line_ok(c["source"]) and line_ok(c["why"])
        assert c["file"].startswith("bench/") and c["file"] not in files
        files.add(c["file"])
        cfg = json.loads((REPO / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"] == []
        for part, key in (("gen", "generator"), ("reference", "reference")):
            assert (REPO / "bench" / part / f"{cfg[key]}.py").is_file()


def test_workloads():
    names = [w["name"] for w in SPEC["workloads"]]
    assert len(names) == len(set(names)) and 1 <= len(names) <= 24
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w for w in SPEC["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(names) // 2)
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line_ok(w["why"])
        assert (REPO / "bench" / "traffic" / f"{w['traffic']}.json").is_file()


def test_metrics():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and line_ok(m["layer"])
        assert m["moves"] in e2e
        assert (REPO / "bench" / "metrics" / f"{m['name']}.py").is_file()
        for w in m.get("workloads", cells):
            assert w in cells
            assert "workloads" not in e2e[m["moves"]] \
                or w in e2e[m["moves"]]["workloads"]
        layers.setdefault(m["layer"], []).append(m["name"])
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = list(e2e) + [m["name"] for m in SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for w in cells:
        mine = [m for m in SPEC["end_to_end"]
                if w in m.get("workloads", [w])]
        assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2
        assert any(w in m.get("workloads", [w]) for m in SPEC["per_layer"])


def test_kernel_metrics_name_their_kernel():
    for m in SPEC["per_layer"]:
        if m["layer"] == "kernels":
            kernel = m["name"].split(".")[0]
            assert (REPO / "bench" / "kernels" / f"{kernel}.py").is_file()
