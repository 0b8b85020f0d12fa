"""Grid push-relabel max-flow vs scipy oracle + invariants (paper §4)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st  # optional-hypothesis shim

from repro.core.maxflow.grid import (GridProblem, check_no_violations,
                                     maxflow_grid)
from repro.core.maxflow.ref import maxflow_grid_ref, random_grid_problem


@pytest.mark.parametrize("seed", range(5))
def test_maxflow_matches_scipy(seed):
    rng = np.random.default_rng(seed)
    H, W = rng.integers(2, 9, 2)
    cap, cs, ct = random_grid_problem(rng, int(H), int(W))
    ref = maxflow_grid_ref(cap, cs, ct)
    res = maxflow_grid(GridProblem(jnp.asarray(cap), jnp.asarray(cs),
                                   jnp.asarray(ct)))
    assert bool(res.converged)
    assert abs(float(res.flow) - ref) < 1e-4
    assert bool(check_no_violations(res.state))


def test_maxflow_32x32():
    rng = np.random.default_rng(42)
    cap, cs, ct = random_grid_problem(rng, 32, 32, max_cap=20,
                                      terminal_density=0.3)
    ref = maxflow_grid_ref(cap, cs, ct)
    res = maxflow_grid(GridProblem(jnp.asarray(cap), jnp.asarray(cs),
                                   jnp.asarray(ct)))
    assert abs(float(res.flow) - ref) < 1e-3


def test_maxflow_pallas_backend_matches():
    rng = np.random.default_rng(3)
    cap, cs, ct = random_grid_problem(rng, 8, 8)
    a = maxflow_grid(GridProblem(jnp.asarray(cap), jnp.asarray(cs),
                                 jnp.asarray(ct)))
    b = maxflow_grid(GridProblem(jnp.asarray(cap), jnp.asarray(cs),
                                 jnp.asarray(ct)), backend="pallas")
    assert float(a.flow) == float(b.flow)


@pytest.mark.parametrize("B,H,W,rounds_per_heuristic,compact", [
    (3, 16, 16, 32, False),     # masked driver, one strip a grid
    (2, 24, 128, 3, False),     # odd cycle: the two-round unroll's tail
    (3, 16, 24, 5, True),       # compacted driver
    (2, 12, 12, 32, True),      # 12 rows: the decide-then-deposit round
])
def test_maxflow_batch_pallas_equals_xla(B, H, W, rounds_per_heuristic,
                                         compact):
    """The whole solve on ``backend="pallas"`` (the fused round where the
    shape allows) gives ``backend="xla"``'s flow, cut and rounds per
    instance, through the masked and the compacted drivers."""
    from repro.core.maxflow.grid import maxflow_grid_batch
    rng = np.random.default_rng(B * H + W)
    probs = [random_grid_problem(rng, H, W) for _ in range(B)]
    prob = GridProblem(*(jnp.asarray(np.stack([p[k] for p in probs]))
                         for k in range(3)))
    want, got = (maxflow_grid_batch(
        prob, backend=backend, rounds_per_heuristic=rounds_per_heuristic,
        compact=compact) for backend in ("xla", "pallas"))
    assert bool(jnp.all(want.converged))
    for field in ("flow", "cut", "rounds", "converged"):
        np.testing.assert_array_equal(np.asarray(getattr(got, field)),
                                      np.asarray(getattr(want, field)),
                                      err_msg=field)


def test_min_cut_separates():
    """Cut labels: cut edges' capacities sum to the flow value (duality)."""
    rng = np.random.default_rng(7)
    cap, cs, ct = random_grid_problem(rng, 6, 6)
    res = maxflow_grid(GridProblem(jnp.asarray(cap), jnp.asarray(cs),
                                   jnp.asarray(ct)))
    cut = np.asarray(res.cut)           # True = sink side
    # source-side -> sink-side original capacities + terminal crossings
    total = 0.0
    H, W = cut.shape
    for i in range(H):
        for j in range(W):
            if not cut[i, j]:           # source side
                total += float(ct[i, j])        # x -> t crossing
                for d, (di, dj) in enumerate([(-1, 0), (1, 0), (0, -1),
                                              (0, 1)]):
                    ii, jj = i + di, j + dj
                    if 0 <= ii < H and 0 <= jj < W and cut[ii, jj]:
                        total += float(cap[d, i, j])
            else:
                total += float(cs[i, j])        # s -> x crossing
    assert abs(total - float(res.flow)) < 1e-3


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000), st.integers(2, 6), st.integers(2, 6))
def test_maxflow_property(seed, H, W):
    """Property: flow value == scipy's for random instances; heights valid."""
    rng = np.random.default_rng(seed)
    cap, cs, ct = random_grid_problem(rng, H, W, max_cap=7)
    ref = maxflow_grid_ref(cap, cs, ct)
    res = maxflow_grid(GridProblem(jnp.asarray(cap), jnp.asarray(cs),
                                   jnp.asarray(ct)))
    assert abs(float(res.flow) - ref) < 1e-4
    assert bool(check_no_violations(res.state))
    # conservation: every interior excess drained
    assert float(jnp.sum(jnp.maximum(res.state.e, 0))) < 1e-4


def test_maxflow_multipush_backend():
    """Beyond-paper multipush variant: same flow value (rounds: see
    EXPERIMENTS.md §Perf — the round-reduction hypothesis was refuted)."""
    rng = np.random.default_rng(11)
    cap, cs, ct = random_grid_problem(rng, 8, 8)
    ref = maxflow_grid_ref(cap, cs, ct)
    r = maxflow_grid(GridProblem(jnp.asarray(cap), jnp.asarray(cs),
                                 jnp.asarray(ct)), backend="multipush")
    assert abs(float(r.flow) - ref) < 1e-4
