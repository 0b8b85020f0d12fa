"""Continuous batching: refill sessions bit-match closed batches, admission
is lossless, and the serving layer resolves futures per instance.

The contract under test (repro.core.refill + repro.serve.scheduler):

* BIT-MATCH — a refilled compacted session delivers, for EVERY request
  (seeded or admitted mid-solve), exactly the result of that request's
  closed-batch solve at the same padding shape: values AND iteration
  counters, for all three registered kinds, regardless of the admission
  schedule.  Checked against the masked driver, the compacted driver, and
  a loop of single solves; on the host and on sharded lanes (2 devices and
  the full emulated count).
* ADMISSION — the ``admit`` hook is offered every freed slot (including
  slots vacated before the first cycle by born-dead instances), may
  decline and be re-offered later, must not over-return, and a payload
  that fails at admission fails ALONE (``on_error``) without aborting the
  session.
* SERVING — with ``AsyncSolverEngine(refill=True)`` queued requests are
  admitted into an in-flight session at cycle boundaries and every
  ticket's future resolves the moment ITS instance converges, not at
  batch drain; a poisoned request admitted mid-solve fails only its own
  future; a session that aborts outright falls back to solo solves so no
  future is lost; the deprecated ``submit_*`` / ``*_kw`` spellings
  warn-and-delegate through the refill path.
* PROPERTY — for random ragged request streams (sizes, kinds, arrival
  order), engine results equal per-request reference solves whatever the
  refill schedule turned out to be (hypothesis when installed, fixed
  seeds otherwise — tests/hypothesis_compat.py).

Timing discipline matches test_scheduler.py: threaded tests assert on
events with generous budgets, never on sleeps; determinism in the
admission tests comes from gating the session INSIDE its finalize hook,
not from racing wall clocks.  Multi-device is emulated exactly as in
test_shard.py: a slow subprocess test relaunches this file under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import os
import pathlib
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.kinds as kinds_mod
from hypothesis_compat import given, settings, st
from repro.core.assignment.ref import optimal_weight
from repro.core.batch import solve_batch
from repro.core.matching.ref import hopcroft_karp, random_bipartite
from repro.core.maxflow.grid import GridProblem
from repro.core.maxflow.ref import maxflow_grid_ref, random_grid_problem
from repro.core.refill import RefillSolver, refill_runtime
from repro.launch.mesh import make_solver_mesh
from repro.serve.engine import SolverEngine
from repro.serve.metrics import SchedulerMetrics
from repro.serve.scheduler import AsyncSolverEngine

pytestmark = pytest.mark.refill

N_DEV = len(jax.devices())
FORCE_FLAG = "--xla_force_host_platform_device_count=8"
multi = pytest.mark.skipif(
    N_DEV < 2, reason="needs >=2 devices; covered via the subprocess test")
SHARD_COUNTS = sorted({2, N_DEV}) if N_DEV >= 2 else []

WAIT_S = 120.0
LONG_DEADLINE_MS = 600_000.0


def _grid(rng, h, w, easy=False):
    cap, cs, ct = random_grid_problem(rng, h, w)
    if easy:
        cs = np.minimum(cs, 1.0)
    return GridProblem(*map(jnp.asarray, (cap, cs, ct)))


def _assert_trees_equal(a, b):
    for name, la, lb in zip(a._fields, a, b):
        if isinstance(la, tuple):  # nested NamedTuple (GridFlowState)
            _assert_trees_equal(la, lb)
        else:
            np.testing.assert_array_equal(
                np.asarray(la), np.asarray(lb), err_msg=name)


def _queue_admit(queue, chunk=None):
    """An ``admit`` callback popping up to ``chunk`` payloads per offer."""
    def admit(n_free):
        take = n_free if chunk is None else min(chunk, n_free)
        out, queue[:take] = list(queue[:take]), []
        return out
    return admit


@pytest.mark.slow  # full refill suite in a fresh 8-dev process
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

def _kind_cases(seed):
    """(kind, shape, payloads) per kind: ragged sizes, ragged difficulty,
    and a born-dead instance where the kind can express one."""
    rng = np.random.default_rng(seed)
    probs = [_grid(rng, 8, 8), _grid(rng, 5, 7, easy=True), _grid(rng, 8, 8),
             _grid(rng, 6, 6, easy=True), _grid(rng, 8, 8, easy=True),
             _grid(rng, 7, 5)]
    ws = [rng.integers(0, 50, (n, n)) for n in (6, 4, 6, 5, 3, 6)]
    adjs = [random_bipartite(rng, 7, 9, 0.25) for _ in range(5)]
    adjs.append(np.zeros((3, 4), bool))          # born-dead: no edges
    return [("maxflow", (8, 8), probs), ("assignment", (6,), ws),
            ("matching", (7, 9), adjs)]


@pytest.mark.parametrize("chunk", [None, 1])
def test_refill_bitmatches_closed_batch_all_kinds(chunk):
    """Seed half, admit the rest mid-solve (all at once or one per offer):
    every result — values and counters — equals the closed-batch solve of
    the same requests at the same padding shape, masked and compacted."""
    for kind, shape, payloads in _kind_cases(0):
        queue = list(payloads[3:])
        got = RefillSolver(kind, shape=shape, capacity=3).run(
            payloads[:3], admit=_queue_admit(queue, chunk))
        assert not queue and sorted(got) == list(range(len(payloads)))
        masked = solve_batch(kind, payloads, bucket="max")
        compacted = solve_batch(kind, payloads, bucket="max", compact=True)
        for i in range(len(payloads)):
            _assert_trees_equal(got[i], masked[i])
            _assert_trees_equal(got[i], compacted[i])


def test_refill_capacity_one_is_a_loop_of_singles():
    """A 1-slot session IS sequential solving: bit-match the loop of
    single solves (same padding shape — all payloads at the bucket max)."""
    rng = np.random.default_rng(1)
    probs = [_grid(rng, 8, 8, easy=bool(i % 2)) for i in range(4)]
    queue = list(probs[1:])
    got = RefillSolver("maxflow", shape=(8, 8), capacity=1).run(
        probs[:1], admit=_queue_admit(queue))
    singles = [solve_batch("maxflow", [p], bucket="max")[0] for p in probs]
    for i, want in enumerate(singles):
        _assert_trees_equal(got[i], want)


def test_refill_underseeded_session_offers_free_slots_before_cycle_zero():
    """Seeding fewer payloads than capacity must offer the empty slots to
    ``admit`` before the first cycle — not leave them inert forever."""
    rng = np.random.default_rng(2)
    ws = [rng.integers(0, 50, (5, 5)) for _ in range(4)]
    offers = []

    def admit(n_free):
        offers.append(n_free)
        out, ws_left[:] = list(ws_left), []
        return out[:n_free]

    ws_left = list(ws[1:])
    got = RefillSolver("assignment", shape=(5,), capacity=4).run(
        ws[:1], admit=admit)
    assert offers[0] == 3, "empty seed slots not offered before cycle 0"
    for i, w in enumerate(ws):
        assert int(got[i].weight) == optimal_weight(w)


def test_refill_decline_then_admit_is_reoffered():
    """Declining an offer must not retire the slot: while anything is
    still live, the hook is offered the freed capacity again at every
    later cycle boundary.  (A decline with NOTHING live ends the session —
    that is the documented quiescence rule, not a retired slot.)"""
    rng = np.random.default_rng(3)
    hard = _grid(rng, 12, 12)
    easies = [_grid(rng, 8, 8, easy=True) for _ in range(2)]
    probs = [hard, easies[0], easies[1]]
    # fine-grained cycles so the easy seed frees its slot several
    # boundaries before the hard one converges
    kw = {"rounds_per_heuristic": 8}
    want = solve_batch("maxflow", probs, bucket="max", **kw)
    assert int(want[0].rounds) >= int(want[1].rounds) + 3 * 8, \
        "hard seed not hard enough — re-offer path untested"
    calls = {"n": 0}

    def admit(n_free):
        calls["n"] += 1
        if calls["n"] < 3:                       # decline twice
            return []
        out, queue[:] = list(queue), []
        return out[:n_free]

    queue = [easies[1]]
    got = RefillSolver("maxflow", shape=(12, 12), capacity=2, **kw).run(
        [hard, easies[0]], admit=admit)
    assert calls["n"] >= 3 and not queue
    for i in range(3):
        _assert_trees_equal(got[i], want[i])


def test_refill_delivers_in_convergence_order():
    """``on_result`` fires the moment an instance converges: an easy
    batch-mate is delivered while the hard seed is still solving."""
    rng = np.random.default_rng(4)
    hard, easy = _grid(rng, 8, 8), _grid(rng, 8, 8, easy=True)
    r_hard, r_easy = solve_batch("maxflow", [hard, easy], bucket="max")
    assert int(r_hard.rounds) > int(r_easy.rounds), \
        "stream not ragged — delivery-order path untested"
    order = []
    RefillSolver("maxflow", shape=(8, 8), capacity=2).run(
        [hard, easy], on_result=lambda i, r: order.append(i))
    assert order == [1, 0], f"delivery order {order} is not convergence order"


@multi
def test_refill_bitmatches_on_sharded_lanes():
    """Refill into per-device compaction lanes (2-way and the full mesh):
    admissions stay within lanes, results still bit-match closed batches."""
    for s in SHARD_COUNTS:
        mesh = make_solver_mesh(s)
        for kind, shape, payloads in _kind_cases(5):
            cap = -(-4 // s) * s                 # divisible across shards
            queue = list(payloads[2:])
            got = RefillSolver(kind, shape=shape, capacity=cap,
                               mesh=mesh).run(payloads[:2],
                                              admit=_queue_admit(queue))
            assert not queue
            want = solve_batch(kind, payloads, bucket="max")
            for i in range(len(payloads)):
                _assert_trees_equal(got[i], want[i])


# ----------------------------------------------------- admission contract

def test_refill_admit_contract():
    rng = np.random.default_rng(6)
    ws = [rng.integers(0, 50, (4, 4)) for _ in range(3)]
    with pytest.raises(ValueError, match="capacity"):
        RefillSolver("assignment", shape=(4,), capacity=0)
    with pytest.raises(ValueError, match="initial payloads"):
        RefillSolver("assignment", shape=(4,), capacity=2).run(ws)
    with pytest.raises(ValueError, match="at most n_free"):
        RefillSolver("assignment", shape=(4,), capacity=1).run(
            ws[:1], admit=lambda n: ws)          # over-returns
    s = RefillSolver("assignment", shape=(4,), capacity=1)
    assert s.fits(ws[0]) and not s.fits(rng.integers(0, 5, (6, 6)))
    # a kind without a registered runtime is a ValueError naming the gap
    real = kinds_mod.get_kind("maxflow")
    kinds_mod._REGISTRY["maxflow"] = real._replace(ops=None)
    try:
        with pytest.raises(ValueError, match="no refill runtime"):
            refill_runtime("maxflow")
    finally:
        kinds_mod._REGISTRY["maxflow"] = real


def test_refill_bad_admission_fails_alone():
    """A payload that fails validation at admission reports through
    ``on_error`` with its own request index; the session continues and
    every other request still bit-matches."""
    rng = np.random.default_rng(7)
    ws = [rng.integers(0, 50, (5, 5)) for _ in range(3)]
    bad = np.ones((5, 5))                        # float: validator rejects
    queue = [ws[1], bad, ws[2]]
    errors = []
    got = RefillSolver("assignment", shape=(5,), capacity=1).run(
        ws[:1], admit=_queue_admit(queue, 1),
        on_error=lambda i, e: errors.append((i, e)))
    assert [i for i, _ in errors] == [2]         # arrival index of ``bad``
    assert isinstance(errors[0][1], ValueError)
    want = solve_batch("assignment", ws, bucket="max")
    for got_i, want_i in zip((got[0], got[1], got[3]), want):
        _assert_trees_equal(got_i, want_i)
    # without on_error the same failure aborts the session
    with pytest.raises(ValueError, match="malformed assignment"):
        RefillSolver("assignment", shape=(5,), capacity=1).run(
            ws[:1], admit=_queue_admit([bad], 1))


# ------------------------------------------------- serving: mid-solve admission

def _gated_refill_factory(real_kind, started, gate, poison=None):
    """Wrap a kind's refill runtime so the FIRST finalize blocks on
    ``gate`` (signalling ``started``) — pinning the session mid-solve so a
    test can submit requests that can only complete via admission — and,
    optionally, so cropping a ``poison``-marked payload raises."""
    def factory(**kw):
        rt = real_kind.ops(**kw)

        def finalize(problems, st1, r):
            if not started.is_set():
                started.set()
                assert gate.wait(timeout=WAIT_S), "test gate never opened"
            return rt.finalize(problems, st1, r)

        def crop(res1, b, shape, payload):
            if poison is not None \
                    and int(np.asarray(payload).ravel()[0]) == poison:
                raise RuntimeError("poisoned crop")
            return rt.crop(res1, b, shape, payload)

        return rt._replace(finalize=finalize, crop=crop)
    return factory


@pytest.mark.serve
def test_async_refill_admits_mid_solve_and_resolves_per_instance(monkeypatch):
    """Deterministic mid-solve admission: the session is pinned inside the
    seed's finalize; requests submitted while it is pinned can ONLY
    complete through cycle-boundary admission (deadline is far away, size
    trigger unreachable), and the seed's future resolves FIRST — per
    instance, not at session drain."""
    started, gate = threading.Event(), threading.Event()
    real = kinds_mod.get_kind("assignment")
    monkeypatch.setitem(
        kinds_mod._REGISTRY, "assignment",
        real._replace(ops=_gated_refill_factory(real, started, gate)))

    rng = np.random.default_rng(8)
    ws = [rng.integers(0, 50, (5, 5)) for _ in range(4)]
    order = []
    with AsyncSolverEngine(max_batch=4, max_delay_ms=LONG_DEADLINE_MS,
                           refill=True) as eng:
        seed_fut = eng.submit("assignment", ws[0])
        seed_fut.add_done_callback(lambda f: order.append("seed"))
        eng.flush_now()                          # open the session
        assert started.wait(timeout=WAIT_S), "session never reached finalize"
        # the session is pinned: these can only resolve via admission
        futs = [eng.submit("assignment", w) for w in ws[1:]]
        for i, f in enumerate(futs):
            f.add_done_callback(lambda _f, i=i: order.append(i))
        gate.set()
        res = [f.result(timeout=WAIT_S) for f in futs]
        assert int(seed_fut.result(timeout=WAIT_S).weight) == \
            optimal_weight(ws[0])
        snap = eng.metrics.snapshot()
    for w, r in zip(ws[1:], res):
        assert int(r.weight) == optimal_weight(w)
    assert order[0] == "seed", \
        f"seed future resolved at {order.index('seed')}, not first: {order}"
    assert snap["refill"]["sessions"].get("assignment", 0) >= 1
    assert snap["refill"]["admitted"].get("assignment", 0) >= 3
    assert snap["refill"]["utilization"] is not None
    assert snap["tickets"]["completed"] == 4


@pytest.mark.serve
def test_async_refill_poison_admitted_mid_solve_fails_alone(monkeypatch):
    """A poisoned request ADMITTED into an in-flight session fails only
    its own future; the seed and the other admissions still resolve."""
    POISON = 777
    started, gate = threading.Event(), threading.Event()
    real = kinds_mod.get_kind("assignment")
    monkeypatch.setitem(
        kinds_mod._REGISTRY, "assignment",
        real._replace(ops=_gated_refill_factory(
            real, started, gate, poison=POISON)))

    rng = np.random.default_rng(9)
    ws = [rng.integers(0, 50, (5, 5)) for _ in range(3)]
    poisoned = ws[1].copy()
    poisoned.flat[0] = POISON
    with AsyncSolverEngine(max_batch=8, max_delay_ms=LONG_DEADLINE_MS,
                           refill=True) as eng:
        seed_fut = eng.submit("assignment", ws[0])
        eng.flush_now()
        assert started.wait(timeout=WAIT_S)
        futs = [eng.submit("assignment", w) for w in (poisoned, ws[2])]
        gate.set()
        with pytest.raises(RuntimeError, match="poisoned"):
            futs[0].result(timeout=WAIT_S)
        assert int(futs[1].result(timeout=WAIT_S).weight) == \
            optimal_weight(ws[2])
        assert int(seed_fut.result(timeout=WAIT_S).weight) == \
            optimal_weight(ws[0])
        snap = eng.metrics.snapshot()
    assert snap["tickets"]["failed"] == 1
    assert snap["tickets"]["completed"] == 2


@pytest.mark.serve
def test_async_refill_session_abort_falls_back_to_solo(monkeypatch):
    """If the session itself detonates (init raises), the lane's
    poison-isolation fallback re-solves every request solo through the
    closed-batch path — no future is ever lost."""
    real = kinds_mod.get_kind("assignment")

    def broken_factory(**kw):
        rt = real.ops(**kw)
        def boom(stacked):
            raise RuntimeError("session init detonated")
        return rt._replace(init=boom)

    monkeypatch.setitem(kinds_mod._REGISTRY, "assignment",
                        real._replace(ops=broken_factory))
    rng = np.random.default_rng(10)
    ws = [rng.integers(0, 50, (5, 5)) for _ in range(3)]
    with AsyncSolverEngine(max_batch=3, max_delay_ms=LONG_DEADLINE_MS,
                           refill=True) as eng:
        futs = [eng.submit("assignment", w) for w in ws]
        for w, f in zip(ws, futs):
            assert int(f.result(timeout=WAIT_S).weight) == optimal_weight(w)


@pytest.mark.serve
def test_async_refill_bitmatches_stream():
    """refill=True serving == closed-batch serving == single solves for a
    recorded mixed-kind stream (the scheduler-level bit-match layer)."""
    rng = np.random.default_rng(11)
    probs = [_grid(rng, 8, 8, easy=bool(i % 2)) for i in range(8)]
    adjs = [random_bipartite(rng, 6, 7, 0.3) for _ in range(4)]
    with AsyncSolverEngine(max_batch=4, max_delay_ms=LONG_DEADLINE_MS,
                           refill=True) as eng:
        f_futs = [eng.submit("maxflow", p) for p in probs]
        m_futs = [eng.submit("matching", a) for a in adjs]
        eng.flush_now()
        f_res = [f.result(timeout=WAIT_S) for f in f_futs]
        m_res = [f.result(timeout=WAIT_S) for f in m_futs]
        snap = eng.metrics.snapshot()
    assert sum(snap["refill"]["sessions"].values()) >= 2
    for lo in range(0, len(probs), 4):           # same 4-chunks as the popper
        want = solve_batch("maxflow", probs[lo:lo + 4], bucket="max")
        for got_i, want_i in zip(f_res[lo:lo + 4], want):
            _assert_trees_equal(got_i, want_i)
    for got_i, want_i in zip(m_res, solve_batch("matching", adjs,
                                                bucket="max")):
        _assert_trees_equal(got_i, want_i)


@pytest.mark.serve
@multi
def test_async_refill_sharded():
    """Continuous batching on a device mesh: sessions run on each lane's
    sub-mesh with capacity rounded to its shard count; results still
    match single solves."""
    for s in SHARD_COUNTS:
        rng = np.random.default_rng(12 + s)
        probs = [_grid(rng, 8, 8, easy=bool(i % 2)) for i in range(10)]
        with AsyncSolverEngine(max_batch=4, max_delay_ms=LONG_DEADLINE_MS,
                               refill=True, mesh=make_solver_mesh(s),
                               n_lanes=2) as eng:
            futs = [eng.submit("maxflow", p) for p in probs]
            eng.flush_now()
            res = [f.result(timeout=WAIT_S) for f in futs]
            snap = eng.metrics.snapshot()
        assert snap["refill"]["sessions"].get("maxflow", 0) >= 1
        for p, r in zip(probs, res):
            assert float(r.flow) == maxflow_grid_ref(
                np.asarray(p.cap_nbr), np.asarray(p.cap_src),
                np.asarray(p.cap_sink))


# --------------------------------------------- deprecated-shim coverage

@pytest.mark.serve
def test_deprecated_spellings_flow_through_refill_path():
    """``submit_maxflow`` / ``submit_assignment`` and the ``*_kw`` ctor
    spellings warn-and-delegate INTO the refill path: the session uses the
    deprecated kwargs and the refill counters prove the route taken."""
    rng = np.random.default_rng(13)
    probs = [_grid(rng, 12, 12) for _ in range(2)]
    ws = [rng.integers(0, 50, (5, 5)) for _ in range(2)]
    # max_rounds far below what these instances need: if the deprecated
    # kwargs were dropped on the refill path, the solves would CONVERGE —
    # the unconverged results below are proof the knob flowed through
    assert all(int(r.rounds) > 32
               for r in solve_batch("maxflow", probs, bucket="max"))
    with pytest.warns(DeprecationWarning, match="maxflow_kw"):
        eng = AsyncSolverEngine(max_batch=2, max_delay_ms=LONG_DEADLINE_MS,
                                refill=True, maxflow_kw={"max_rounds": 32})
    with eng:
        with pytest.warns(DeprecationWarning, match="submit_maxflow"):
            f_futs = [eng.submit_maxflow(p) for p in probs]
        with pytest.warns(DeprecationWarning, match="submit_assignment"):
            a_futs = [eng.submit_assignment(w) for w in ws]
        f_res = [f.result(timeout=WAIT_S) for f in f_futs]
        a_res = [f.result(timeout=WAIT_S) for f in a_futs]
        snap = eng.metrics.snapshot()
    assert snap["refill"]["sessions"].get("maxflow", 0) >= 1
    assert snap["refill"]["sessions"].get("assignment", 0) >= 1
    assert all(not bool(r.converged) and int(r.rounds) == 32 for r in f_res)
    want = solve_batch("maxflow", probs, bucket="max", max_rounds=32)
    for got_i, want_i in zip(f_res, want):
        _assert_trees_equal(got_i, want_i)
    for w, r in zip(ws, a_res):
        assert int(r.weight) == optimal_weight(w)


def test_sync_engine_refill_session_inherits_solver_kw():
    """``SolverEngine.refill_session`` folds the engine's per-kind solver
    kwargs (deprecated spellings included) into the session."""
    with pytest.warns(DeprecationWarning, match="maxflow_kw"):
        eng = SolverEngine(maxflow_kw={"max_rounds": 32})
    rng = np.random.default_rng(14)
    probs = [_grid(rng, 12, 12) for _ in range(2)]
    got = eng.refill_session("maxflow", shape=(12, 12), capacity=2).run(probs)
    assert all(not bool(got[i].converged) for i in range(2))
    want = solve_batch("maxflow", probs, bucket="max", max_rounds=32)
    for i in range(2):
        _assert_trees_equal(got[i], want[i])


# ----------------------------------------------------------- metrics unit

def test_refill_metrics_snapshot():
    m = SchedulerMetrics(ewma_alpha=1.0)
    snap = m.snapshot()["refill"]
    assert snap == {"sessions": {}, "admitted": {},
                    "slot_occupancy_ewma": {}, "utilization": None}
    m.record_refill_session("maxflow")
    m.record_refill_admit("maxflow", 3)
    m.record_refill_cycle("maxflow", 1.0)
    m.record_refill_cycle("maxflow", 0.5)
    snap = m.snapshot()["refill"]
    assert snap["sessions"] == {"maxflow": 1}
    assert snap["admitted"] == {"maxflow": 3}
    assert snap["slot_occupancy_ewma"]["maxflow"] == 0.5   # alpha=1: last
    assert snap["utilization"] == 0.75                     # mean of cycles


# ------------------------------------------------- property: ragged streams

def _check_stream(seed):
    """One random ragged stream through ``AsyncSolverEngine(refill=True)``:
    random sizes, kinds, and arrival order; every future must equal its
    per-request REFERENCE solve no matter how the refill schedule fell."""
    rng = np.random.default_rng(seed)
    reqs = []                                    # (kind, payload, checker)
    for _ in range(int(rng.integers(6, 13))):
        k = int(rng.integers(3))
        if k == 0:
            h, w = int(rng.integers(4, 9)), int(rng.integers(4, 9))
            p = _grid(rng, h, w, easy=bool(rng.integers(2)))
            ref = maxflow_grid_ref(np.asarray(p.cap_nbr),
                                   np.asarray(p.cap_src),
                                   np.asarray(p.cap_sink))
            reqs.append(("maxflow", p,
                         lambda r, ref=ref: float(r.flow) == ref))
        elif k == 1:
            n = int(rng.integers(3, 7))
            w = rng.integers(0, 50, (n, n))
            ref = optimal_weight(w)
            reqs.append(("assignment", w,
                         lambda r, ref=ref: int(r.weight) == ref))
        else:
            nl, nr = int(rng.integers(3, 8)), int(rng.integers(3, 8))
            a = random_bipartite(rng, nl, nr, float(rng.uniform(0.1, 0.5)))
            ref = hopcroft_karp(a)[2]
            reqs.append(("matching", a,
                         lambda r, ref=ref: int(r.cardinality) == ref))
    # pow2 bucketing keeps the compile-shape set small across examples
    with AsyncSolverEngine(max_batch=int(rng.integers(2, 5)),
                           max_delay_ms=float(rng.uniform(1.0, 20.0)),
                           refill=True, bucket="pow2",
                           n_lanes=int(rng.integers(1, 3))) as eng:
        futs = [eng.submit(kind, payload) for kind, payload, _ in reqs]
        if rng.integers(2):
            eng.flush_now()
        results = [f.result(timeout=WAIT_S) for f in futs]
    for (kind, _, check), r in zip(reqs, results):
        assert check(r), f"{kind} result diverged from reference (seed " \
                         f"{seed})"


@pytest.mark.serve
@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=0, max_value=2**32 - 1))
def test_property_ragged_streams_match_references(seed):
    _check_stream(seed)


@pytest.mark.serve
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fixed_seed_ragged_streams_match_references(seed):
    """The hypothesis property above pinned to fixed seeds, so the stream
    invariant is exercised even where hypothesis is not installed."""
    _check_stream(seed)
