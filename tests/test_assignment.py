"""Cost-scaling assignment vs Hungarian oracle + ε-optimality (paper §5)."""
import importlib.util
import json
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st  # optional-hypothesis shim

from repro.core import solve_batch
from repro.core.assignment.cost_scaling import (_RefineState, price_update,
                                                solve_assignment)
from repro.core.assignment.ref import (eps_optimal, optimal_weight,
                                       optimal_weight_bruteforce)


@pytest.mark.parametrize("method", ["pushrelabel", "auction"])
@pytest.mark.parametrize("seed", range(4))
def test_assignment_optimal(method, seed):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(2, 24))
    w = rng.integers(0, 101, size=(n, n))
    res = solve_assignment(jnp.asarray(w), method=method)
    assert bool(res.converged)
    assert int(res.weight) == optimal_weight(w)
    # a perfect matching (permutation)
    assert sorted(np.asarray(res.col_of_row).tolist()) == list(range(n))


def test_assignment_negative_and_tiny():
    rng = np.random.default_rng(9)
    w = rng.integers(-50, 51, size=(6, 6))
    res = solve_assignment(jnp.asarray(w))
    assert int(res.weight) == optimal_weight(w)
    assert int(res.weight) == optimal_weight_bruteforce(np.asarray(w))
    w1 = np.asarray([[7]])
    assert int(solve_assignment(jnp.asarray(w1)).weight) == 7


@pytest.mark.parametrize("kw", [
    dict(use_price_update=False, use_arc_fixing=False),
    dict(use_price_update=True, use_arc_fixing=False),
    dict(use_price_update=False, use_arc_fixing=True),
    dict(method="pushrelabel", rounds_per_heuristic=4),
])
def test_assignment_heuristic_ablations(kw):
    rng = np.random.default_rng(1)
    w = rng.integers(0, 101, size=(12, 12))
    res = solve_assignment(jnp.asarray(w), **kw)
    assert int(res.weight) == optimal_weight(w)


def test_assignment_pallas_backend():
    rng = np.random.default_rng(2)
    w = rng.integers(0, 101, size=(16, 16))
    for method in ["pushrelabel", "auction"]:
        res = solve_assignment(jnp.asarray(w), method=method,
                               backend="pallas")
        assert int(res.weight) == optimal_weight(w)


def test_paper_operating_point():
    """Paper §6: complete bipartite, |X|=|Y|<=30, costs <= 100."""
    rng = np.random.default_rng(2011)
    w = rng.integers(0, 101, size=(30, 30))
    res = solve_assignment(jnp.asarray(w), method="pushrelabel")
    assert int(res.weight) == optimal_weight(w)


def _one_optimal(w, res) -> bool:
    """The matching of ``res`` is 1-optimal w.r.t. its final prices."""
    n = w.shape[0]
    F = np.zeros((n, n), np.int32)
    F[np.arange(n), np.asarray(res.col_of_row)] = 1
    return eps_optimal(w, F, np.asarray(res.p_x), np.asarray(res.p_y), eps=1)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.integers(2, 12),
       st.sampled_from(["pushrelabel", "auction"]))
def test_assignment_property(seed, n, method):
    """Property: optimality + the auction invariant that prices of Y only
    decrease (paper Lemma 5.2 in Goldberg price coordinates)."""
    rng = np.random.default_rng(seed)
    w = rng.integers(0, 64, size=(n, n))
    res = solve_assignment(jnp.asarray(w), method=method)
    assert bool(res.converged)
    assert int(res.weight) == optimal_weight(w)
    # final pseudoflow is 1-optimal wrt final prices (scaled costs)
    assert _one_optimal(w, res)


def test_final_prices_one_optimal_with_arc_fixing():
    """Regression: with arc fixing and the price update both on, the final
    prices are 1-optimal over every arc, fixed ones included (the price
    update once left fixed arcs out of its distance graph)."""
    w = np.random.default_rng(0).integers(0, 64, size=(6, 6))
    res = solve_assignment(jnp.asarray(w), method="pushrelabel")
    assert bool(res.converged)
    assert int(res.weight) == optimal_weight(w)
    assert _one_optimal(w, res)


@pytest.mark.parametrize("seed,n", [(10, 6), (11, 6), (6, 8)])
def test_price_update_keeps_fixed_arcs_eps_optimal(seed, n):
    """``price_update`` on an ε-optimal pseudoflow with fixed arcs leaves
    every arc, fixed or not, within ε of optimality."""
    eps = 1
    rng = np.random.default_rng(seed)
    w = rng.integers(0, 64, size=(n, n))
    res = solve_assignment(jnp.asarray(w), method="pushrelabel")
    c = -(n + 1) * w
    col = np.asarray(res.col_of_row)
    F = np.zeros((n, n), np.int32)
    F[np.arange(n), col] = 1
    p_x, p_y = np.asarray(res.p_x), np.asarray(res.p_y)
    cp = c + p_x[:, None] - p_y[None, :]
    fixed = (cp > 2 * n * eps) & (F == 0)
    # unmatch two rows whose arc stays ε-optimal as a forward arc: the
    # state is ε-optimal, with deficits for the update to measure from
    safe = np.flatnonzero(cp[np.arange(n), col] >= -eps)
    F[rng.choice(safe, 2, replace=False)] = 0
    assert fixed.any() and eps_optimal(w, F, p_x, p_y, eps)
    zero = jnp.zeros((), jnp.int32)
    st = _RefineState(F=jnp.asarray(F), p_x=jnp.asarray(p_x, jnp.int32),
                      p_y=jnp.asarray(p_y, jnp.int32),
                      fixed=jnp.asarray(fixed), rounds=zero, pushes=zero,
                      relabels=zero)
    out = price_update(jnp.asarray(c, jnp.int32), jnp.int32(eps), st,
                       max_sweeps=2 * n)
    assert eps_optimal(w, F, np.asarray(out.p_x), np.asarray(out.p_y), eps)


def _bench_file(*parts):
    return pathlib.Path(__file__).resolve().parents[1].joinpath(
        "bench", *parts)


@pytest.mark.parametrize("max_cost", [100, 10_000])
@pytest.mark.parametrize("n", [16, 64, 128])
def test_dense_assign_cell_path_matches_reference(n, max_cost):
    """The ``dense_assign_1024`` benchmark cell's own path — ``solve_batch``
    with the configuration's solver settings (auction on the ``pallas``
    bidding kernel, interpreted here), 4 matrices a call — against the
    benchmark's plain reference. At costs 0..100 the optimum is often
    ``100·n``; costs 0..10⁴ stress the ε ladder."""
    cfg = json.loads(_bench_file("configs", "dense_assign_1024.json")
                     .read_text())
    spec = importlib.util.spec_from_file_location(
        "assignment_dense_reference",
        _bench_file("reference", "assignment_dense.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    rng = np.random.default_rng(1000 * n + max_cost)
    ws = [rng.integers(0, max_cost + 1, size=(n, n), dtype=np.int32)
          for _ in range(cfg["batch"])]
    for w, res in zip(ws, solve_batch("assignment", ws, **cfg["solver_kw"])):
        col = np.asarray(res.col_of_row)
        assert bool(res.converged)
        assert np.array_equal(np.sort(col), np.arange(n))
        assert int(w[np.arange(n), col].astype(np.int64).sum()) \
            == int(res.weight) == ref.solve(w)
