"""The matching kind end-to-end: oracle equality + the full invariant stack.

This file is the registry's acceptance test (ISSUE: "prove the seam with a
third kind"): bipartite maximum-cardinality matching
(``repro.core.matching``, lock-free BFS augmenting-path phases after
Deveci et al., arXiv:1303.1379) must ride EVERY layer the original two
kinds ride — ragged pad-and-bucket, pow2 bucketing, mesh sharding,
early-exit compaction, the sync engine, and the async scheduler — with no
changes to those layers, and hold the same bit-match contract at each:

* CORRECTNESS — cardinality equals the NumPy Hopcroft–Karp oracle on
  random and adversarial instances (hidden perfect matching, star,
  block-diagonal/disconnected), and every reported matching is a valid
  matching of the input graph;
* batched == a loop of single solves (every leaf, including rounds);
* kernel == reference — the pallas frontier-expansion kernel bit-matches
  the pure-jnp oracle tile-by-tile, and ``backend="pallas"`` bit-matches
  ``backend="xla"`` end-to-end;
* sharded == unsharded (2 and the full emulated device count, with inert
  shard padding for non-divisible queues);
* compacted == masked; async futures == sync flush.

Multi-device is emulated exactly as in test_shard.py: a slow subprocess
test relaunches this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; CI also runs the
file directly with the flag exported.
"""
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batch import solve_batch
from repro.core.kinds import get_kind
from repro.core.matching import (MatchingResult, hopcroft_karp,
                                 match_bipartite, match_bipartite_batch,
                                 prepare_matching_buckets,
                                 validate_matching_problem)
from repro.core.matching.ref import (disconnected_instance,
                                     perfect_matching_instance,
                                     random_bipartite, star_instance)
from repro.kernels.frontier.kernel import INF, frontier
from repro.kernels.frontier.ref import frontier_ref
from repro.launch.mesh import make_solver_mesh
from repro.serve.engine import SolverEngine

N_DEV = len(jax.devices())
FORCE_FLAG = "--xla_force_host_platform_device_count=8"
multi = pytest.mark.skipif(
    N_DEV < 2, reason="needs >=2 devices; covered via the subprocess test")
SHARD_COUNTS = sorted({2, N_DEV}) if N_DEV >= 2 else []


def _assert_results_equal(a: MatchingResult, b: MatchingResult):
    for name, la, lb in zip(a._fields, a, b):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb), err_msg=name)


def _assert_valid_matching(adj: np.ndarray, res: MatchingResult):
    """The reported matching is a real matching OF THIS GRAPH."""
    mr = np.asarray(res.match_row)
    mc = np.asarray(res.match_col)
    for i, j in enumerate(mr):
        if j >= 0:
            assert adj[i, j], f"matched non-edge ({i}, {j})"
            assert mc[j] == i, f"inconsistent match_col at col {j}"
    for j, i in enumerate(mc):
        if i >= 0:
            assert mr[i] == j, f"inconsistent match_row at row {i}"
    assert int(res.cardinality) == int(np.sum(mr >= 0))


@pytest.mark.slow  # full matching suite in a fresh 8-device process
@pytest.mark.skipif(N_DEV >= 2, reason="already multi-device")
def test_forced_multi_device_subprocess():
    """Relaunch this file under 8 emulated host devices and require green."""
    root = pathlib.Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + FORCE_FLAG).strip()
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-x", "-q", str(__file__)],
        cwd=root, env=env, capture_output=True, text=True, timeout=1500)
    assert r.returncode == 0, f"\n--- stdout ---\n{r.stdout}\n{r.stderr}"
    assert "passed" in r.stdout


# ------------------------------------------------------- oracle equality

def test_cardinality_matches_hopcroft_karp_random():
    rng = np.random.default_rng(0)
    for t in range(25):
        nl, nr = int(rng.integers(1, 24)), int(rng.integers(1, 24))
        adj = random_bipartite(rng, nl, nr, p=float(rng.uniform(0.05, 0.6)))
        _, _, card = hopcroft_karp(adj)
        res = match_bipartite(adj)
        assert int(res.cardinality) == card, (t, nl, nr)
        assert bool(res.converged), "Berge certificate missing"
        _assert_valid_matching(adj, res)


def test_cardinality_matches_oracle_adversarial():
    rng = np.random.default_rng(1)
    # hidden perfect matching: the answer must be exactly n, and greedy
    # init must not strand rows that only long alternating paths recover
    for n in (4, 9, 17):
        adj = perfect_matching_instance(rng, n)
        for greedy_init in (True, False):
            res = match_bipartite(adj, greedy_init=greedy_init)
            assert int(res.cardinality) == n
            _assert_valid_matching(adj, res)
    # star: every tree fights for one column; exactly one may win
    for nl, nr, hub in ((7, 5, 0), (12, 6, 4), (1, 1, 0)):
        res = match_bipartite(star_instance(nl, nr, hub=hub))
        assert int(res.cardinality) == 1
    # disconnected blocks incl. isolated vertices (zero blocks)
    for _ in range(5):
        adj = disconnected_instance(
            rng, [(3, 2), (0, 4), (5, 5), (2, 0), (1, 1)])
        _, _, card = hopcroft_karp(adj)
        res = match_bipartite(adj)
        assert int(res.cardinality) == card
        _assert_valid_matching(adj, res)
    # fully empty graph: converges in 0 rounds
    res = match_bipartite(np.zeros((4, 6), bool))
    assert int(res.cardinality) == 0 and int(res.rounds) == 0
    assert bool(res.converged)


# ----------------------------------------------------- batched == single

def test_batched_equals_loop_of_single_solves():
    rng = np.random.default_rng(2)
    adjs = [random_bipartite(rng, 9, 11, p=0.25) for _ in range(6)]
    batched = match_bipartite_batch(jnp.asarray(np.stack(adjs)))
    for b, adj in enumerate(adjs):
        solo = match_bipartite(adj)
        _assert_results_equal(
            MatchingResult(*(np.asarray(l)[b] for l in batched)), solo)


def test_single_instance_rejects_batched_input_and_vice_versa():
    with pytest.raises(ValueError, match="ONE instance"):
        match_bipartite(np.zeros((2, 3, 3), bool))
    with pytest.raises(ValueError, match="single instance"):
        match_bipartite_batch(np.zeros((3, 3), bool))


# ------------------------------------------------------ kernel == oracle

def test_frontier_kernel_matches_reference():
    rng = np.random.default_rng(3)
    for nl, nr, br, bc in ((8, 8, 8, 8), (16, 32, 4, 8), (12, 24, 3, 24)):
        adj = jnp.asarray(random_bipartite(rng, nl, nr, p=0.3))
        root = jnp.where(jnp.asarray(rng.random(nl) < 0.5),
                         jnp.arange(nl, dtype=jnp.int32), INF)
        match = jnp.asarray(
            rng.integers(-1, nr, nl).astype(np.int32))
        got = frontier(adj, root, match, block_rows=br, block_cols=bc,
                       interpret=True)
        want = frontier_ref(adj, root, match)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_pallas_backend_bitmatches_xla_end_to_end():
    rng = np.random.default_rng(4)
    for shape in ((8, 8), (16, 8)):
        adj = random_bipartite(rng, *shape, p=0.3)
        rx = match_bipartite(adj, backend="xla")
        rp = match_bipartite(adj, backend="pallas")
        _assert_results_equal(rx, rp)
    # batched too (the pallas op is vmapped over the batch axis)
    adjs = np.stack([random_bipartite(rng, 8, 8) for _ in range(4)])
    _assert_results_equal(
        match_bipartite_batch(jnp.asarray(adjs), backend="xla"),
        match_bipartite_batch(jnp.asarray(adjs), backend="pallas"))


# ----------------------------------------------- ragged front end (batch)

def test_ragged_front_end_matches_single_solves():
    rng = np.random.default_rng(5)
    adjs = [random_bipartite(rng, int(rng.integers(1, 14)),
                             int(rng.integers(1, 14)))
            for _ in range(9)]
    for bucket in ("max", "pow2", "exact"):
        outs = solve_batch("matching", adjs, bucket=bucket)
        for adj, r in zip(adjs, outs):
            assert r.match_row.shape == (adj.shape[0],)
            assert r.match_col.shape == (adj.shape[1],)
            _assert_valid_matching(adj, r)
            _, _, card = hopcroft_karp(adj)
            assert int(r.cardinality) == card


def test_prepare_buckets_pads_and_stacks():
    rng = np.random.default_rng(6)
    adjs = [random_bipartite(rng, 3, 5), random_bipartite(rng, 7, 2)]
    [prep] = prepare_matching_buckets(adjs, bucket="max")
    assert prep.kind == "matching" and prep.shape == (7, 5)
    assert prep.stacked.shape == (2, 7, 5)
    assert prep.stacked.dtype == jnp.bool_
    # padding is edge-less: the pad region holds no True entry
    assert not np.asarray(prep.stacked)[0, 3:, :].any()
    assert not np.asarray(prep.stacked)[1, :, 2:].any()


def test_compacted_equals_masked():
    rng = np.random.default_rng(7)
    adjs = np.stack([random_bipartite(rng, 10, 10, p=p)
                     for p in (0.05, 0.5, 0.1, 0.9, 0.2)])
    _assert_results_equal(
        match_bipartite_batch(jnp.asarray(adjs), compact=False),
        match_bipartite_batch(jnp.asarray(adjs), compact=True))


# ------------------------------------------------------------- sharding

@multi
def test_sharded_equals_unsharded():
    rng = np.random.default_rng(8)
    adjs = jnp.asarray(np.stack(
        [random_bipartite(rng, 8, 12) for _ in range(8)]))
    base = match_bipartite_batch(adjs)
    for s in SHARD_COUNTS:
        got = match_bipartite_batch(adjs, mesh=make_solver_mesh(s))
        _assert_results_equal(base, got)


@multi
def test_sharded_ragged_queue_inert_padding():
    """A queue size not divisible by the shard count rides the front end's
    inert padding; results still match the unsharded ragged solve."""
    rng = np.random.default_rng(9)
    adjs = [random_bipartite(rng, int(rng.integers(2, 10)),
                             int(rng.integers(2, 10)))
            for _ in range(5)]                      # 5 % 2 != 0
    base = solve_batch("matching", adjs)
    for s in SHARD_COUNTS:
        got = solve_batch("matching", adjs, mesh=make_solver_mesh(s))
        for b, g in zip(base, got):
            _assert_results_equal(b, g)


@multi
def test_sharded_compacted_equals_masked():
    rng = np.random.default_rng(10)
    adjs = jnp.asarray(np.stack(
        [random_bipartite(rng, 8, 8) for _ in range(8)]))
    mesh = make_solver_mesh(2)
    _assert_results_equal(
        match_bipartite_batch(adjs, mesh=mesh),
        match_bipartite_batch(adjs, mesh=mesh, compact=True))


# ----------------------------------------------------------- serve layer

def test_sync_engine_serves_matching_with_zero_engine_changes():
    rng = np.random.default_rng(11)
    mesh = make_solver_mesh() if N_DEV >= 2 else None
    engine = SolverEngine(mesh=mesh,
                          solver_kw={"matching": {"backend": "xla"}})
    adjs = [random_bipartite(rng, n, n) for n in (4, 6, 4)]
    tickets = [engine.submit("matching", a) for a in adjs]
    # edge-list payloads canonicalize through the registered validator
    t_edge = engine.submit(
        "matching", (np.array([[0, 1], [1, 0]]), (2, 2)))
    out = engine.flush()
    assert sorted(out) == tickets + [t_edge]
    base = solve_batch("matching", adjs, mesh=mesh)
    for t, b in zip(tickets, base):
        _assert_results_equal(out[t], b)
    assert int(out[t_edge].cardinality) == 2


@pytest.mark.serve
def test_async_scheduler_serves_matching():
    """Futures bit-match the sync flush of the same chunks — the matching
    kind rides the scheduler with zero scheduler changes."""
    from repro.serve.scheduler import AsyncSolverEngine
    rng = np.random.default_rng(12)
    adjs = [random_bipartite(rng, 8, 8) for _ in range(8)]
    with AsyncSolverEngine(max_batch=4, max_delay_ms=600_000.0) as eng:
        futs = [eng.submit("matching", a) for a in adjs]
        res = [f.result(timeout=120.0) for f in futs]
        assert eng.metrics.convergence.spread("matching") is not None
        snap = eng.metrics.snapshot()
    assert "matching" in snap["spread_ewma"]

    sync = SolverEngine()
    base = []
    for lo in range(0, len(adjs), 4):
        ts = [sync.submit("matching", a) for a in adjs[lo:lo + 4]]
        out = sync.flush()
        base += [out[t] for t in ts]
    for got, want in zip(res, base):
        _assert_results_equal(got, want)


# ----------------------------------------------------- validator rejects

def test_validator_rejects_malformed_payloads():
    # non-0/1 entries are not a bipartite adjacency
    with pytest.raises(ValueError, match="0/1"):
        validate_matching_problem(np.array([[0, 2], [1, 0]]))
    with pytest.raises(ValueError, match="malformed matching"):
        validate_matching_problem(np.zeros((3,)))           # 1-D
    with pytest.raises(ValueError, match="empty side"):
        validate_matching_problem(np.zeros((0, 3), bool))
    with pytest.raises(ValueError, match="negative vertex id"):
        validate_matching_problem((np.array([[0, -1]]), (2, 2)))
    with pytest.raises(ValueError, match="out of range"):
        validate_matching_problem((np.array([[0, 5]]), (2, 2)))
    with pytest.raises(ValueError, match="integer vertex ids"):
        validate_matching_problem((np.array([[0.5, 1.0]]), (2, 2)))
    # rejected before any ticket exists
    engine = SolverEngine()
    with pytest.raises(ValueError, match="malformed matching"):
        engine.submit("matching", np.array([[0, 2], [1, 0]]))
    assert engine.pending() == 0


def test_validator_canonicalizes_good_payloads():
    a = validate_matching_problem([[1, 0], [0, 1]])
    assert a.dtype == bool and a.shape == (2, 2)
    e = validate_matching_problem(
        (np.array([[0, 0], [1, 2], [1, 0]]), (2, 3)))
    assert e.shape == (2, 3) and e.sum() == 3 and e[1, 2]


# ----------------------------------------------------------- registration

def test_matching_kind_registration_surface():
    kind = get_kind("matching")
    assert kind.name == "matching"
    inert = kind.inert_problem((4, 6))
    assert inert.shape == (4, 6) and not inert.any()
    # the cached ops factory returns the SAME ops (and spec) for equal knobs
    assert kind.ops() is kind.ops()
    assert kind.ops(max_rounds=7).spec is not kind.ops().spec
