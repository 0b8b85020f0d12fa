"""The plain references against the repository's own oracles, and the
comparisons against answers known right and known wrong."""
import numpy as np
import pytest

from bench.gen import assign_uniform, grid_uniform
from bench.reference import assignment_dense, maxflow_grid


@pytest.mark.parametrize("shape", [(5, 7), (16, 16), (9, 32)])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_maxflow_reference_matches_repo_oracle(shape, seed):
    from repro.core.maxflow.ref import maxflow_grid_ref
    rng = np.random.default_rng(seed)
    inst = grid_uniform.instance(rng, *shape, max_cap=20,
                                 terminal_density=0.3)
    assert maxflow_grid.solve(inst) == maxflow_grid_ref(*inst)


@pytest.mark.parametrize("family", ["checkerboard", "random_wide",
                                    "long_path"])
def test_maxflow_reference_on_adversarial_families(family):
    from repro.core.maxflow.ref import (ADVERSARIAL_GENERATORS,
                                        maxflow_grid_ref)
    inst = ADVERSARIAL_GENERATORS[family](np.random.default_rng(3), 24, 40)
    assert maxflow_grid.solve(inst) == maxflow_grid_ref(*inst)


def test_arcs_leave_out_off_grid_directions():
    inst = grid_uniform.instance(np.random.default_rng(4), 4, 6, 20, 0.3)
    cap = inst[0].copy()
    cap[:] = 5.0                    # capacities on off-grid arcs too
    tails, heads, caps, n = maxflow_grid.arcs((cap, inst[1], inst[2]))
    grid = (tails < n) & (heads < n)
    # 4x6 grid: 2 * (3*6 + 4*5) = 76 arcs between cells
    assert int(grid.sum()) == 76


def test_cut_capacity_of_the_program_cut_is_the_flow():
    from repro.core import solve_batch
    rng = np.random.default_rng(5)
    insts = [grid_uniform.instance(rng, 16, 128, 20, 0.3) for _ in range(2)]
    for inst, res in zip(insts, solve_batch("maxflow", insts,
                                            backend="pallas")):
        ref = maxflow_grid.solve(inst)
        answer = {"flow": np.asarray(res.flow), "cut": np.asarray(res.cut),
                  "converged": np.asarray(res.converged)}
        assert maxflow_grid.compare(inst, answer, ref) == {
            "flow_gap": 0, "cut_gap": 0, "unconverged": 0}


def test_cut_capacity_catches_a_cut_that_is_not_minimum():
    inst = grid_uniform.instance(np.random.default_rng(6), 12, 12, 20, 0.3)
    ref = maxflow_grid.solve(inst)
    all_source = np.zeros((12, 12), bool)      # cut = every sink arc
    assert maxflow_grid.cut_capacity(inst, all_source) == int(inst[2].sum())
    bad = {"flow": ref, "cut": all_source, "converged": True}
    assert maxflow_grid.compare(inst, bad, ref)["cut_gap"] == \
        int(inst[2].sum()) - ref > 0


def test_cut_capacity_by_hand():
    # 1x2 grid: s -3-> a -2-> b -4-> t ; min cut is the a->b arc
    cap = np.zeros((4, 1, 2), np.float32)
    cap[3, 0, 0] = 2                                   # a RIGHT -> b
    cs = np.array([[3, 0]], np.float32)
    ct = np.array([[0, 4]], np.float32)
    inst = (cap, cs, ct)
    assert maxflow_grid.solve(inst) == 2
    assert maxflow_grid.cut_capacity(inst, np.array([[False, True]])) == 2
    assert maxflow_grid.cut_capacity(inst, np.array([[True, True]])) == 3
    assert maxflow_grid.cut_capacity(inst, np.array([[False, False]])) == 4


@pytest.mark.parametrize("seed", range(5))
def test_assignment_reference_matches_brute_force(seed):
    from repro.core.assignment.ref import optimal_weight_bruteforce
    [w] = assign_uniform.pool(np.random.default_rng(seed),
                              {"n": 6, "max_cost": 100, "pool": 1})
    assert assignment_dense.solve(w) == optimal_weight_bruteforce(w)


def test_assignment_compare():
    [w] = assign_uniform.pool(np.random.default_rng(7),
                              {"n": 8, "max_cost": 100, "pool": 1})
    ref = assignment_dense.solve(w)
    from scipy.optimize import linear_sum_assignment
    _, col = linear_sum_assignment(w, maximize=True)
    good = {"col_of_row": col, "weight": ref, "converged": True}
    assert assignment_dense.compare(w, good, ref) == {
        "not_permutation": 0, "weight_gap": 0, "reported_gap": 0,
        "unconverged": 0}
    dup = good | {"col_of_row": np.zeros(8, int)}
    assert assignment_dense.compare(w, dup, ref)["not_permutation"] == 1
    assert assignment_dense.compare(w, dup, ref)["weight_gap"] > 0
    worst = good | {"col_of_row": np.argmin(w, axis=1)}
    if np.array_equal(np.sort(worst["col_of_row"]), np.arange(8)):
        assert assignment_dense.compare(w, worst, ref)["weight_gap"] > 0
    lie = good | {"weight": ref + 1}
    assert assignment_dense.compare(w, lie, ref)["reported_gap"] == 1
