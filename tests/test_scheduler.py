"""Async serving scheduler: futures bit-match the blocking path, triggers
fire without manual flushes, shutdown never hangs, poison stays isolated.

The contract under test (repro.serve.scheduler.AsyncSolverEngine):

* BIT-MATCH — the scheduler decides WHEN and WHERE the tested batch path
  runs, never what it computes: for a recorded request stream, async
  futures == synchronous ``SolverEngine.flush()`` of the same chunks ==
  a loop of single solves. Checked on the plain, sharded (2 and the full
  emulated device count), and compacted paths.
* TRIGGERS — a kind flushes when ``max_batch`` requests are queued (size)
  or a request's ``deadline_ms`` expires (deadline), with no manual
  flush; ``close(drain=True)`` resolves everything pending,
  ``close(drain=False)`` cancels queued futures. Neither hangs.
* ADAPTIVE DISPATCH — per-bucket masked-vs-compacted choice follows the
  convergence-spread EWMA (ragged streams go compacted, uniform streams
  stay masked), with ``dispatch=`` as the forced override.
* ISOLATION — a request that makes the batched dispatch raise fails only
  its own future; batch-mates still get results.

Timing discipline: these tests are THREADED — every wait uses a generous
budget (the ``serve`` marker's contract, see pyproject.toml) and asserts
on events, never on sleeps. Multi-device is emulated exactly as in
test_shard.py: a slow subprocess test relaunches this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``; CI also runs the
file directly with the flag exported.
"""
import os
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.kinds as kinds_mod
from repro.core.assignment.cost_scaling import solve_assignment
from repro.core.batch import solve_maxflow_batch
from repro.core.maxflow.grid import GridProblem, maxflow_grid
from repro.core.maxflow.ref import random_grid_problem
from repro.core.solver_loop import trace_cycles
from repro.launch.mesh import make_solver_mesh, scheduler_lanes, shard_count
from repro.serve.engine import SolverEngine
from repro.serve.metrics import (ConvergenceStats, Ewma, LatencyWindow,
                                 SchedulerMetrics)
from repro.serve.scheduler import AsyncSolverEngine, choose_driver

pytestmark = pytest.mark.serve

N_DEV = len(jax.devices())
FORCE_FLAG = "--xla_force_host_platform_device_count=8"
multi = pytest.mark.skipif(
    N_DEV < 2, reason="needs >=2 devices; covered via the subprocess test")
SHARD_COUNTS = sorted({2, N_DEV}) if N_DEV >= 2 else []

# generous budgets: thread-timing tests must not flake on slow CI workers
WAIT_S = 120.0
LONG_DEADLINE_MS = 600_000.0


def _grid_problems(seed, B, H, W):
    rng = np.random.default_rng(seed)
    return [GridProblem(*map(jnp.asarray, random_grid_problem(rng, H, W)))
            for _ in range(B)]


def _ragged_grid_problems(seed, B, H, W):
    """Most instances converge in the first cycles, a few run long —
    the convergence-spread signal adaptive dispatch keys on."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(B):
        cap, cs, ct = random_grid_problem(rng, H, W)
        if i % 4:                        # 3 of every 4 are easy
            cs = np.minimum(cs, 1.0)
        out.append(GridProblem(*map(jnp.asarray, (cap, cs, ct))))
    return out


def _assert_trees_equal(a, b):
    for name, la, lb in zip(a._fields, a, b):
        if isinstance(la, tuple):  # nested NamedTuple (GridFlowState)
            _assert_trees_equal(la, lb)
        else:
            np.testing.assert_array_equal(
                np.asarray(la), np.asarray(lb), err_msg=name)


@pytest.mark.slow  # ~2 min: full scheduler suite in a fresh 8-dev process
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


# ------------------------------------------------------------- bit-match

def _bitmatch_stream(async_kw: dict, sync_kw: dict, chunk: int = 4):
    """Submit a recorded stream both ways; futures must tree-equal the
    synchronous flush of the same chunks."""
    probs = _grid_problems(0, 2 * chunk, 8, 8)
    ws = [np.random.default_rng(i).integers(0, 50, (6, 6))
          for i in range(chunk)]
    with AsyncSolverEngine(max_batch=chunk,
                           max_delay_ms=LONG_DEADLINE_MS, **async_kw) as eng:
        f_futs = [eng.submit("maxflow", p) for p in probs]
        a_futs = [eng.submit("assignment", w) for w in ws]
        eng.flush_now()                  # the assignment chunk is short
        f_res = [f.result(timeout=WAIT_S) for f in f_futs]
        a_res = [f.result(timeout=WAIT_S) for f in a_futs]

    sync = SolverEngine(**sync_kw)
    base_f, base_a = [], []
    for lo in range(0, len(probs), chunk):
        ts = [sync.submit("maxflow", p) for p in probs[lo:lo + chunk]]
        out = sync.flush()
        base_f += [out[t] for t in ts]
    ts = [sync.submit("assignment", w) for w in ws]
    out = sync.flush()
    base_a += [out[t] for t in ts]

    for got, want in zip(f_res + a_res, base_f + base_a):
        _assert_trees_equal(got, want)
    return f_res, a_res, probs, ws


def test_async_bitmatch_plain_vs_sync_and_single():
    f_res, a_res, probs, ws = _bitmatch_stream({"dispatch": "masked"}, {})
    # ... and the loop-of-single-solves layer of the contract
    for got, p in zip(f_res, probs):
        single = maxflow_grid(p)
        assert float(got.flow) == float(single.flow)
        assert int(got.rounds) == int(single.rounds)
        np.testing.assert_array_equal(np.asarray(got.cut),
                                      np.asarray(single.cut))
    for got, w in zip(a_res, ws):
        single = solve_assignment(jnp.asarray(w))
        assert int(got.weight) == int(single.weight)
        np.testing.assert_array_equal(np.asarray(got.col_of_row),
                                      np.asarray(single.col_of_row))


def test_async_bitmatch_compacted():
    _bitmatch_stream({"dispatch": "compacted"}, {"compact": True})


@multi
def test_async_bitmatch_sharded():
    """Sharded scheduler (lanes on disjoint sub-meshes when the mesh is
    big enough) == sharded sync flush == the UNSHARDED sync flush."""
    for s in SHARD_COUNTS:
        _bitmatch_stream({"mesh": make_solver_mesh(s), "n_lanes": 2,
                          "dispatch": "masked"}, {})


def test_async_bitmatch_ragged_exact_bucket():
    """bucket="exact" means results are independent of batch composition
    entirely — async == single solves for a ragged shape mix."""
    rng = np.random.default_rng(3)
    shapes = [(5, 5), (8, 8), (4, 7), (8, 8), (5, 5), (4, 7)]
    probs = [GridProblem(*map(jnp.asarray, random_grid_problem(rng, h, w)))
             for h, w in shapes]
    with AsyncSolverEngine(max_batch=3, max_delay_ms=LONG_DEADLINE_MS,
                           bucket="exact", dispatch="masked") as eng:
        futs = [eng.submit("maxflow", p) for p in probs]
        res = [f.result(timeout=WAIT_S) for f in futs]
    for got, p in zip(res, probs):
        single = maxflow_grid(p)
        assert float(got.flow) == float(single.flow)
        assert int(got.rounds) == int(single.rounds)
        np.testing.assert_array_equal(np.asarray(got.cut),
                                      np.asarray(single.cut))


# ------------------------------------------------------------- triggers

def test_deadline_trigger_completes_without_flush():
    """A lone request (far below max_batch) completes inside its deadline
    budget with NO manual flush — the background thread did it."""
    [p] = _grid_problems(4, 1, 8, 8)
    with AsyncSolverEngine(max_batch=64, max_delay_ms=250.0) as eng:
        t0 = time.monotonic()
        fut = eng.submit("maxflow", p)
        res = fut.result(timeout=WAIT_S)
        elapsed = time.monotonic() - t0
        snap = eng.metrics.snapshot()
    assert bool(res.converged)
    assert snap["flushes_by_trigger"].get("deadline", 0) >= 1
    assert snap["flushes_by_trigger"].get("size", 0) == 0
    # generous sanity bound, not a tight latency assertion (serve marker)
    assert elapsed < WAIT_S


def test_size_trigger_fires_at_max_batch():
    probs = _grid_problems(5, 4, 8, 8)
    with AsyncSolverEngine(max_batch=4,
                           max_delay_ms=LONG_DEADLINE_MS) as eng:
        eng.flush_now()          # empty queue: must NOT arm a stale manual
        futs = [eng.submit("maxflow", p) for p in probs]
        res = [f.result(timeout=WAIT_S) for f in futs]
        snap = eng.metrics.snapshot()
    assert all(bool(r.converged) for r in res)
    # the batch flushed on SIZE — a stale manual flag would have dispatched
    # the first submission as a singleton 'manual' batch instead
    assert snap["flushes_by_trigger"].get("manual", 0) == 0
    assert snap["flushes_by_trigger"].get("size", 0) >= 1
    assert snap["tickets"]["completed"] == 4
    assert snap["latency_ms"]["p50"] is not None
    assert snap["latency_ms"]["p99"] >= snap["latency_ms"]["p50"]


def test_shutdown_drains_pending_futures():
    probs = _grid_problems(6, 3, 8, 8)
    eng = AsyncSolverEngine(max_batch=64, max_delay_ms=LONG_DEADLINE_MS)
    futs = [eng.submit("maxflow", p) for p in probs]
    eng.close(drain=True)                # must not hang, must resolve all
    for f in futs:
        assert bool(f.result(timeout=1.0).converged)
    assert eng.metrics.snapshot()["flushes_by_trigger"].get("drain", 0) >= 1
    eng.close()                          # idempotent


def test_shutdown_cancels_when_not_draining():
    probs = _grid_problems(7, 2, 8, 8)
    eng = AsyncSolverEngine(max_batch=64, max_delay_ms=LONG_DEADLINE_MS)
    futs = [eng.submit("maxflow", p) for p in probs]
    eng.close(drain=False)
    assert all(f.cancelled() for f in futs)
    assert eng.metrics.snapshot()["tickets"]["cancelled"] == 2
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit("maxflow", probs[0])


def test_submit_validates_before_future_exists():
    good = _grid_problems(8, 1, 6, 6)[0]
    bad = GridProblem(good.cap_nbr, -good.cap_src, good.cap_sink)
    with AsyncSolverEngine(max_batch=4, max_delay_ms=LONG_DEADLINE_MS) as eng:
        with pytest.raises(ValueError, match="negative"):
            eng.submit("maxflow", bad)
        with pytest.raises(ValueError, match="malformed assignment"):
            eng.submit("assignment", np.ones((3, 4)))
        assert eng.pending() == 0
        assert eng.metrics.snapshot()["tickets"].get("submitted", 0) == 0


# ------------------------------------------------------------- isolation

def test_poisoned_request_fails_only_its_own_future(monkeypatch):
    """A request that detonates the batched dispatch gets its exception;
    every batch-mate still resolves with a correct result."""
    POISON = 777

    real = kinds_mod.get_kind("assignment")

    def maybe_boom(prep, **kw):
        if any(int(np.asarray(o).ravel()[0]) == POISON
               for o in prep.originals):
            raise RuntimeError("poisoned dispatch")
        return real.solve_prepared(prep, **kw)

    monkeypatch.setitem(kinds_mod._REGISTRY, "assignment",
                        real._replace(solve_prepared=maybe_boom))

    rng = np.random.default_rng(9)
    ws = [rng.integers(0, 50, (5, 5)) for _ in range(3)]
    poisoned = ws[1].copy()
    poisoned.flat[0] = POISON
    stream = [ws[0], poisoned, ws[2]]
    with AsyncSolverEngine(max_batch=3, max_delay_ms=LONG_DEADLINE_MS) as eng:
        futs = [eng.submit("assignment", w) for w in stream]
        with pytest.raises(RuntimeError, match="poisoned"):
            futs[1].result(timeout=WAIT_S)
        for f, w in ((futs[0], ws[0]), (futs[2], ws[2])):
            got = f.result(timeout=WAIT_S)
            single = solve_assignment(jnp.asarray(w))
            assert int(got.weight) == int(single.weight)
        snap = eng.metrics.snapshot()
    assert snap["tickets"]["failed"] == 1
    assert snap["tickets"]["completed"] == 2


def test_failed_batch_is_counted_isolated_and_logged(monkeypatch, caplog):
    """A batched dispatch that raises is logged with its exception, and
    every solo re-solve it falls back to counts as an ``isolated``
    dispatch in the metrics and in the Prometheus exposition."""
    from repro.obs import prometheus_text

    real = kinds_mod.get_kind("assignment")
    calls = []

    def fail_first_batch(prep, **kw):
        calls.append(len(prep.originals))
        if len(calls) == 1:
            raise RuntimeError("batched dispatch refused")
        return real.solve_prepared(prep, **kw)

    monkeypatch.setitem(kinds_mod._REGISTRY, "assignment",
                        real._replace(solve_prepared=fail_first_batch))
    rng = np.random.default_rng(11)
    ws = [rng.integers(0, 50, (5, 5)) for _ in range(3)]
    with caplog.at_level("ERROR", logger="repro.serve.scheduler"):
        with AsyncSolverEngine(max_batch=3,
                               max_delay_ms=LONG_DEADLINE_MS) as eng:
            futs = [eng.submit("assignment", w) for w in ws]
            for f, w in zip(futs, ws):
                got = f.result(timeout=WAIT_S)
                assert int(got.weight) == int(
                    solve_assignment(jnp.asarray(w)).weight)
    assert calls[0] == 3
    assert eng.metrics.dispatch_count("assignment", "isolated") == 3
    assert eng.metrics.dispatch_count("assignment", "masked") == 0
    assert ('repro_dispatches_total{kind="assignment",driver="isolated"} 3'
            in prometheus_text(eng.metrics))
    [rec] = [r for r in caplog.records if "re-solving" in r.getMessage()]
    assert "batched dispatch refused" in rec.exc_text


# ----------------------------------------------------- adaptive dispatch

def test_adaptive_dispatch_chooses_compaction_on_ragged_stream():
    """First chunk runs masked (no history); once the spread EWMA builds,
    ragged-convergence chunks flip to the compacted driver."""
    probs = _ragged_grid_problems(10, 12, 8, 8)
    with AsyncSolverEngine(max_batch=4, max_delay_ms=LONG_DEADLINE_MS,
                           dispatch="adaptive", spread_threshold=0.1,
                           min_compact_batch=2) as eng:
        for lo in range(0, len(probs), 4):
            futs = [eng.submit("maxflow", p) for p in probs[lo:lo + 4]]
            [f.result(timeout=WAIT_S) for f in futs]   # serialize chunks
        m = eng.metrics
        spread = m.convergence.spread("maxflow")
        masked = m.dispatch_count("maxflow", "masked")
        compacted = m.dispatch_count("maxflow", "compacted")
    assert spread is not None and spread > 0.1, \
        "stream not ragged — adaptive path untested"
    assert masked >= 1, "first dispatch (no history) should stay masked"
    assert compacted >= 1, "EWMA never flipped the driver to compacted"


def test_adaptive_dispatch_stays_masked_on_uniform_stream():
    # a truly uniform stream: the same instance repeated — identical
    # trajectories, zero round spread, so compaction never pays
    probs = _grid_problems(11, 1, 8, 8) * 8
    with AsyncSolverEngine(max_batch=4, max_delay_ms=LONG_DEADLINE_MS,
                           dispatch="adaptive", spread_threshold=0.1,
                           min_compact_batch=2) as eng:
        for lo in range(0, len(probs), 4):
            futs = [eng.submit("maxflow", p) for p in probs[lo:lo + 4]]
            [f.result(timeout=WAIT_S) for f in futs]
        assert eng.metrics.dispatch_count("maxflow", "compacted") == 0


def test_forced_dispatch_override():
    probs = _grid_problems(12, 4, 8, 8)
    with AsyncSolverEngine(max_batch=4, max_delay_ms=LONG_DEADLINE_MS,
                           dispatch="compacted") as eng:
        futs = [eng.submit("maxflow", p) for p in probs]
        [f.result(timeout=WAIT_S) for f in futs]
        assert eng.metrics.dispatch_count("maxflow", "masked") == 0
        assert eng.metrics.dispatch_count("maxflow", "compacted") >= 1
    with pytest.raises(ValueError, match="dispatch"):
        AsyncSolverEngine(dispatch="warp-speed")


def test_choose_driver_policy_table():
    kw = dict(threshold=0.25, min_batch=4)
    assert choose_driver(None, 8, forced="adaptive", **kw) is False
    assert choose_driver(0.1, 8, forced="adaptive", **kw) is False
    assert choose_driver(0.5, 8, forced="adaptive", **kw) is True
    assert choose_driver(0.5, 2, forced="adaptive", **kw) is False  # tiny
    assert choose_driver(0.5, 2, forced="compacted", **kw) is True
    assert choose_driver(0.9, 64, forced="masked", **kw) is False


# ------------------------------------------------- trace hook + metrics

def test_cycle_trace_hook_sees_live_set_shrink():
    """repro.core.solver_loop.trace_cycles: the compacted driver reports
    (cycle, n_live) per host cycle, and the live set only shrinks."""
    probs = _ragged_grid_problems(13, 6, 8, 8)
    calls: list[tuple[int, int]] = []
    with trace_cycles(lambda c, n: calls.append((c, n))):
        solve_maxflow_batch(probs, compact=True)
    assert calls, "compacted solve traced no cycles"
    assert calls[0] == (0, 6)
    lives = [n for _, n in calls]
    assert all(a >= b for a, b in zip(lives, lives[1:])), \
        f"live set grew: {lives}"
    # hook uninstalled outside the context
    calls.clear()
    solve_maxflow_batch(probs, compact=True)
    assert not calls


def test_metrics_primitives():
    e = Ewma(alpha=0.5)
    assert e.value is None
    assert e.update(1.0) == 1.0
    assert e.update(0.0) == 0.5
    with pytest.raises(ValueError):
        Ewma(alpha=0.0)

    w = LatencyWindow(maxlen=4)
    assert w.percentiles()["p50"] is None
    for x in (1.0, 2.0, 3.0, 4.0, 100.0):   # 1.0 evicted
        w.record(x)
    p = w.percentiles()
    assert p["p50"] == 3.5 and p["p99"] > 4.0 and len(w) == 4

    c = ConvergenceStats(alpha=1.0)
    assert c.spread("maxflow") is None
    c.observe("maxflow", spread=0.5, occupancy=0.75)
    assert c.spread("maxflow") == 0.5 and c.occupancy("maxflow") == 0.75

    m = SchedulerMetrics()
    m.record_submit(3)
    m.record_dispatch("maxflow", compact=True, spread=0.4, occupancy=0.5)
    m.record_live_trace(0, 8)
    m.record_live_trace(1, 4)
    snap = m.snapshot()
    assert snap["queue_depth"] == 3
    assert snap["dispatches"] == {"maxflow:compacted": 1}
    assert snap["compact_cycles"] == 2 and snap["compact_live_mean"] == 6.0


# ------------------------------------------------------ scheduler lanes

def test_scheduler_lanes_no_mesh():
    assert scheduler_lanes(None, None, 3) == [None, None, None]
    with pytest.raises(ValueError, match="n_lanes"):
        scheduler_lanes(None, None, 0)


def test_scheduler_lanes_single_device_shares_mesh():
    mesh = make_solver_mesh(1)
    lanes = scheduler_lanes(mesh, None, 2)
    assert len(lanes) == 2 and all(l is mesh for l in lanes)


@multi
def test_scheduler_lanes_split_devices_disjoint():
    mesh = make_solver_mesh()            # all devices
    lanes = scheduler_lanes(mesh, None, 2)
    assert len(lanes) == 2
    devs = [d for l in lanes for d in l.devices.reshape(-1)]
    assert len(devs) == N_DEV == len(set(devs)), "lanes overlap"
    assert sum(shard_count(l) for l in lanes) == N_DEV
