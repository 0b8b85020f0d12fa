"""The trace reduction, on intervals by hand and on a trace from the chip.

``data/chip_small.xplane.pb`` was recorded on one TPU v5 lite: two 64x128
max-flow grids and one n=256 auction through ``solve_batch`` (Pallas
kernels), inside a ``bench:window`` annotation.
"""
import numpy as np
import pytest

from bench import trace
from bench.kernels import bidding, grid_push
from test_rehearsal import CHIP_TRACE


def test_union_length():
    assert trace.union_length([]) == 0
    assert trace.union_length([(0, 1), (2, 3)]) == 2
    assert trace.union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4
    assert trace.union_length([(3, 4), (0, 10)]) == 10


def test_gaps():
    assert trace.gaps([], 0, 5) == [(0, 5)]
    assert trace.gaps([(1, 2), (1.5, 3), (4, 9)], 0, 5) == [(0, 1), (3, 4)]
    assert trace.gaps([(0, 5)], 0, 5) == []


def test_label_prefers_most_overlap_then_shorter():
    names = ["outer", "a", "b", "late"]
    starts = np.array([0.0, 2, 2, 9.5])
    ends = np.array([10.0, 3, 5, 12])
    assert trace.label((2, 5), names, starts, ends) == "b"
    assert trace.label((2, 3), names, starts, ends) == "a"
    assert trace.label((11, 15), names, starts, ends) == "late"
    assert trace.label((20, 21), names, starts, ends) == "no host activity"


def test_leaves_drop_ops_that_hold_others():
    evs = [("while", 0, 10), ("a", 1, 2), ("b", 2, 4), ("c", 3.999, 6),
           ("d", 11, 12)]
    assert [n for n, *_ in trace.leaves(evs)] == ["a", "b", "c", "d"]


def test_op_names():
    text = "%grid_push_decide.4 = (s32[8]{0}) custom-call(%fusion.85)"
    assert trace.op_name(text) == "grid_push_decide.4"
    assert trace.op_name("%fusion.9 = f32[2] fusion(%grid_push_decide.4)") \
        == "fusion.9"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(str(CHIP_TRACE), n_chips=1, kernels={
        "grid_push": grid_push.MATCH, "bidding": bidding.MATCH})


def test_chip_trace_window_and_busy(reduced):
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    assert reduced["busy_per_chip_s"] == [reduced["busy_s"]]


def test_chip_trace_finds_both_kernels(reduced):
    for k in ("grid_push", "bidding"):
        assert reduced["kernels"][k]["calls"] > 0
        assert 0 < reduced["kernels"][k]["seconds"] < reduced["busy_s"]


def test_chip_trace_breakdown(reduced):
    ops, idle = reduced["top_ops"], reduced["idle_gaps"]
    assert 0 < len(ops) <= 10 and 0 < len(idle) <= 10
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    assert [t for _, t in idle] == sorted((t for _, t in idle), reverse=True)
    assert sum(t for _, t in ops) <= reduced["busy_s"] * (1 + 1e-9)
    assert all(isinstance(n, str) and n for n, _ in idle)


def test_missing_window_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        trace.find_xplane(tmp_path)
