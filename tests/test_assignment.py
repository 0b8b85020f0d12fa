"""Cost-scaling assignment vs Hungarian oracle + ε-optimality (paper §5)."""
import importlib.util
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st  # optional-hypothesis shim

from repro.core import solve_batch
from repro.core.assignment.cost_scaling import (INF, _RefineState,
                                                _round_auction, _scale_init,
                                                price_update,
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


def _round_auction_gather(c, eps, st, *, backend):
    """The auction round as it read ``won`` before: gathers of ``winner`` and
    ``got_bid`` at each row's ``arg1``. The reference for the round's
    gather-free ``won``."""
    F, p_x, p_y, fixed = st.F, st.p_x, st.p_y, st.fixed
    n = c.shape[-1]
    e1 = jnp.asarray(eps)[..., None]
    active_x = jnp.sum(F, axis=-1) == 0
    if backend == "pallas":
        from repro.kernels.bidding.ops import bidding_op
        op = bidding_op
        for _ in range(c.ndim - 2):
            op = jax.vmap(op)
        min1, arg1, min2 = op(c, p_y, fixed)
    else:
        cpx = jnp.where(fixed, INF, c - p_y[..., None, :])
        min1 = jnp.min(cpx, axis=-1)
        arg1 = jnp.argmin(cpx, axis=-1)
        cpx2 = jnp.where(jax.nn.one_hot(arg1, n, dtype=bool), INF, cpx)
        min2 = jnp.min(cpx2, axis=-1)
    min2 = jnp.where(min2 >= INF, min1, min2)
    bids = jnp.where(
        (jnp.arange(n) == arg1[..., :, None]) & active_x[..., :, None],
        (min1 - min2 - e1)[..., :, None], INF)
    best_bid = jnp.min(bids, axis=-2)
    winner = jnp.argmin(bids, axis=-2)
    got_bid = best_bid < INF
    new_match = jax.nn.one_hot(winner, n, dtype=F.dtype, axis=-2) \
        * got_bid[..., None, :].astype(F.dtype)
    F = F * (~got_bid)[..., None, :].astype(F.dtype) + new_match
    p_y = jnp.where(got_bid, p_y + best_bid, p_y)
    winner_at = jnp.take_along_axis(winner, arg1, axis=-1)
    won = active_x & (winner_at == jnp.arange(n)) \
        & jnp.take_along_axis(got_bid, arg1, axis=-1)
    p_x = jnp.where(won, -(min2 + e1), p_x)
    n_push = jnp.sum(got_bid, axis=-1)
    return _RefineState(F=F, p_x=p_x, p_y=p_y, fixed=fixed,
                        rounds=st.rounds + 1, pushes=st.pushes + n_push,
                        relabels=st.relabels + n_push)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("batch", [(), (3,), (2, 2)])
@pytest.mark.parametrize("max_cost", [1, 3, 100])
def test_auction_round_matches_gather_reference(max_cost, batch, backend):
    """The auction round reads ``won`` as the row-any of its ``new_match``;
    over 32 rounds from a refine entry with a random ``fixed`` mask (one
    row fixed whole), on tied and spread costs and batch ranks 0-2, every
    ``_RefineState`` leaf equals the gather formula's, round by round."""
    n = 16
    rng = np.random.default_rng(7 * max_cost + len(batch))
    w = rng.integers(0, max_cost + 1, size=batch + (n, n))
    s = _scale_init(jnp.asarray(w), alpha=10)
    fixed = rng.random(batch + (n, n)) < 0.3
    fixed[..., n // 2, :] = True
    st = ref = s.st._replace(fixed=jnp.asarray(fixed))
    new = jax.jit(lambda c, e, t: _round_auction(c, e, t, backend=backend))
    old = jax.jit(lambda c, e, t: _round_auction_gather(c, e, t,
                                                        backend=backend))
    for _ in range(32):
        st, ref = new(s.c, s.eps, st), old(s.c, s.eps, ref)
        for name, a, b in zip(_RefineState._fields, st, ref):
            assert np.array_equal(np.asarray(a), np.asarray(b)), name


def test_auction_round_lowers_without_gather():
    """The auction round has no gather: on TPU one with data-dependent
    indices runs as a serial loop, and the round once spent most of its
    device time in two of them."""
    w = np.random.default_rng(0).integers(0, 101, size=(2, 16, 16))
    s = _scale_init(jnp.asarray(w), alpha=10)
    text = jax.jit(lambda c, e, t: _round_auction(c, e, t, backend="xla")) \
        .lower(s.c, s.eps, s.st).as_text()
    assert "gather" not in text


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
