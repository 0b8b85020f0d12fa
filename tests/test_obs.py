"""Observability: span tracing, solver-loop telemetry, export surfaces.

The contract under test (repro.obs + the instrumentation it hooks into):

* TRACER — ``repro.obs.Tracer`` records spans lock-free from many
  threads at once, tracks per-thread nesting (parent ids), exports a
  plain event list and valid Chrome-trace JSON, and round-trips through
  ``save``/``load_trace``.
* LIFECYCLE RECONSTRUCTION — a traced ``AsyncSolverEngine`` session
  (closed-batch, refill, and sharded) yields, for EVERY resolved ticket,
  a complete ``submit -> queue-wait -> solve -> resolve`` chain with
  consistent, monotonic span boundaries; refill-admitted tickets carry
  ``trigger="refill"`` and a ``refill-admission`` span.
* CYCLE TELEMETRY — ``repro.core.solver_loop.cycle_events`` streams
  structured per-cycle events from BOTH the masked and compacted
  drivers, for all three solver kinds; ``trace_cycles`` stays a working
  back-compat shim.
* BIT-MATCH — tracing enabled vs disabled changes NOTHING about solver
  outputs (values and counters) on the masked, compacted, and refill
  paths. Telemetry observes; it never steers.
* EXPORT — ``prometheus_text`` renders every ``SchedulerMetrics``
  snapshot field (completeness enforced: unknown keys raise), and
  ``benchmarks.run --trace`` writes a valid Chrome-trace file plus a
  ``wall_s`` column in the CSV.
* HYGIENE — the instrumented non-shim serving paths run clean under
  ``-W error::DeprecationWarning``, and ``SchedulerMetrics.snapshot()``
  returns a deep copy.

Multi-device is emulated as in test_shard.py: CI also runs this file
under ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
"""
import json
import pathlib
import sys
import threading
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.kinds as kinds_mod
from repro.core import (GridProblem, cycle_events, maxflow_grid_batch,
                        match_bipartite_batch, solve_assignment,
                        trace_cycles)
from repro.core.maxflow.ref import random_grid_problem
from repro.core.refill import RefillSolver
from repro.launch.mesh import make_solver_mesh
from repro.obs import (Tracer, current_tracer, load_trace, prometheus_text,
                       step_annotation, use_tracer)
from repro.serve.engine import SolverEngine
from repro.serve.metrics import Ewma, LatencyWindow, SchedulerMetrics
from repro.serve.scheduler import AsyncSolverEngine

pytestmark = pytest.mark.obs

N_DEV = len(jax.devices())
multi = pytest.mark.skipif(
    N_DEV < 2, reason="needs >=2 devices; CI runs this file under the "
                      "forced 8-device flag")

WAIT_S = 120.0
LONG_DEADLINE_MS = 600_000.0

LIFECYCLE = {"submit", "queue-wait", "solve", "resolve"}


# ------------------------------------------------------------ helpers

def _grid_problems(seed, B, H, W):
    rng = np.random.default_rng(seed)
    return [GridProblem(*map(jnp.asarray, random_grid_problem(rng, H, W)))
            for _ in range(B)]


def _grid_batch(seed, B, H, W):
    rng = np.random.default_rng(seed)
    return GridProblem(
        jnp.asarray(rng.integers(0, 5, (B, 4, H, W)), jnp.float32),
        jnp.asarray(rng.integers(0, 6, (B, H, W)), jnp.float32),
        jnp.asarray(rng.integers(0, 6, (B, H, W)), jnp.float32))


def _assert_trees_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _ticket_chains(tracer: Tracer) -> dict:
    """Group lifecycle spans by their ``ticket`` attribute."""
    chains: dict = {}
    for s in tracer.spans():
        t = s.attrs.get("ticket")
        if t is not None:
            chains.setdefault(t, []).append(s)
    return chains


def _check_lifecycle(chains: dict, tickets) -> None:
    """Every ticket has a full, gap-consistent, monotonic span chain."""
    for t in tickets:
        assert t in chains, f"ticket {t} left no spans"
        by_name = {}
        for s in chains[t]:
            assert s.t0 <= s.t1, f"span {s.name} of ticket {t} runs backwards"
            by_name.setdefault(s.name, s)
        assert LIFECYCLE <= set(by_name), \
            f"ticket {t} missing stages: {LIFECYCLE - set(by_name)}"
        # submit ends where queue-wait begins; each later stage starts no
        # earlier than the previous one ended
        assert abs(by_name["submit"].t1 - by_name["queue-wait"].t0) < 1e-9
        assert by_name["queue-wait"].t1 <= by_name["solve"].t0 + 1e-9
        assert by_name["solve"].t1 <= by_name["resolve"].t0 + 1e-9


# ------------------------------------------------------------ tracer core

def test_span_nesting_tracks_parent_ids():
    tr = Tracer()
    with tr.span("outer", kind="maxflow"):
        with tr.span("inner", step=1):
            pass
        with tr.span("inner2"):
            pass
    with tr.span("top"):
        pass
    spans = {s.name: s for s in tr.spans()}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["inner2"].parent_id == spans["outer"].span_id
    assert spans["outer"].parent_id is None
    assert spans["top"].parent_id is None
    assert spans["outer"].attrs == {"kind": "maxflow"}
    # inner spans finish (and are appended) before their parent
    assert [s.name for s in tr.spans()] == ["inner", "inner2", "outer", "top"]
    ids = [s.span_id for s in tr.spans()]
    assert len(set(ids)) == len(ids)


def test_record_and_instant_spans():
    tr = Tracer()
    sid = tr.record("queue-wait", 10.0, 12.5, ticket=7)
    tr.instant("mark", cycle=3)
    qw, mark = tr.spans()
    assert (qw.name, qw.t0, qw.t1, qw.span_id) == ("queue-wait", 10.0, 12.5,
                                                   sid)
    assert qw.attrs == {"ticket": 7}
    assert mark.t0 == mark.t1 and mark.attrs == {"cycle": 3}
    tr.clear()
    assert tr.spans() == []


def test_chrome_export_structure():
    tr = Tracer()
    with tr.span("device-solve", kind="matching", bucket=[8, 8]):
        pass
    doc = tr.to_chrome()
    assert doc["displayTimeUnit"] == "ms"
    (ev,) = doc["traceEvents"]
    assert ev["ph"] == "X" and ev["name"] == "device-solve"
    assert ev["dur"] >= 0 and isinstance(ev["ts"], float)
    assert ev["args"]["kind"] == "matching"
    assert ev["args"]["bucket"] == [8, 8]
    assert "span_id" in ev["args"] and "parent_id" in ev["args"]
    json.dumps(doc)  # must be JSON-serializable as-is


def test_save_load_roundtrip(tmp_path):
    tr = Tracer()
    tr.record("solve", 1.0, 2.0, ticket=0)
    path = tmp_path / "trace.json"
    tr.save(path)
    events = load_trace(path)
    assert len(events) == 1 and events[0]["name"] == "solve"
    # the bare event-array form of the Chrome-trace spec loads too
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps(events))
    assert load_trace(bare) == events
    bad = tmp_path / "bad.json"
    bad.write_text('{"not": "a trace"}')
    with pytest.raises((ValueError, KeyError)):
        load_trace(bad)


def test_tracer_concurrent_recording():
    """Many threads record nested spans at once: nothing is lost, ids stay
    unique, and nesting never leaks across threads."""
    tr = Tracer()
    n_threads, n_spans = 8, 100
    barrier = threading.Barrier(n_threads)

    def worker(k):
        barrier.wait()
        for i in range(n_spans):
            with tr.span("outer", worker=k, i=i):
                with tr.span("inner", worker=k, i=i):
                    pass

    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spans = tr.spans()
    assert len(spans) == n_threads * n_spans * 2
    ids = {s.span_id for s in spans}
    assert len(ids) == len(spans)
    outer_by_tid = {}
    for s in spans:
        if s.name == "outer":
            outer_by_tid.setdefault(s.tid, set()).add(s.span_id)
    for s in spans:
        if s.name == "inner":
            assert s.parent_id in outer_by_tid[s.tid], \
                "inner span parented across threads"


def test_ambient_tracer_contextvar():
    assert current_tracer() is None
    tr = Tracer()
    with use_tracer(tr) as got:
        assert got is tr and current_tracer() is tr
        with use_tracer(None):
            assert current_tracer() is None
        assert current_tracer() is tr
    assert current_tracer() is None


def test_step_annotation_is_harmless_without_profiler():
    with step_annotation("solve:maxflow", bucket="8x8"):
        x = jnp.zeros((2, 2)) + 1
    assert float(x.sum()) == 4.0


# ------------------------------------------------------- cycle telemetry

def test_cycle_events_masked_maxflow_bitmatch():
    prob = _grid_batch(0, 5, 6, 6)
    base = maxflow_grid_batch(prob)
    evs = []
    with cycle_events(evs.append, masked=True, detail=True):
        traced = maxflow_grid_batch(prob)
    assert evs, "masked driver emitted no cycle events"
    assert all(e.driver == "masked" for e in evs)
    assert [e.cycle for e in evs] == list(range(len(evs)))
    lives = [e.n_live for e in evs]
    assert lives == sorted(lives, reverse=True), \
        f"masked live counts not monotone: {lives}"
    assert lives[0] == 5
    assert all(e.gathered == 5 for e in evs), \
        "masked driver dispatches the full batch every cycle"
    assert all(e.heur_total is not None and e.heur_total >= 0 for e in evs)
    rt = [e.rounds_total for e in evs]
    assert rt == sorted(rt)
    _assert_trees_equal(base, traced)


def test_cycle_events_compacted_maxflow_bitmatch():
    prob = _grid_batch(1, 6, 6, 6)
    base = maxflow_grid_batch(prob, compact=True)
    evs = []
    with cycle_events(evs.append, detail=True):
        traced = maxflow_grid_batch(prob, compact=True)
    assert evs and all(e.driver == "compacted" for e in evs)
    assert [e.cycle for e in evs] == list(range(len(evs)))
    lives = [e.n_live for e in evs]
    assert lives == sorted(lives, reverse=True)
    # compaction gathers pow2 buckets: the dispatch width tracks, but
    # never undercuts, the live count
    assert all(e.gathered >= e.n_live for e in evs)
    assert all(e.heur_total is not None for e in evs)
    _assert_trees_equal(base, traced)


def test_cycle_events_masked_needs_optin():
    """Without masked=True the masked driver stays one fused dispatch and
    emits nothing (jit caches must never depend on ambient hooks)."""
    prob = _grid_batch(2, 3, 6, 6)
    evs = []
    with cycle_events(evs.append):              # compacted-only by default
        maxflow_grid_batch(prob)
    assert evs == []


def test_cycle_events_all_kinds_bitmatch():
    rng = np.random.default_rng(3)
    w = jnp.asarray(rng.integers(0, 9, (4, 5, 5)), jnp.int32)
    adj = jnp.asarray(rng.random((4, 6, 6)) < 0.4)
    for solve in (lambda: solve_assignment(w),
                  lambda: match_bipartite_batch(adj)):
        base = solve()
        evs = []
        with cycle_events(evs.append, masked=True):
            traced = solve()
        assert evs and evs[0].driver == "masked"
        assert evs[0].heur_total is None        # detail=False skips the fetch
        _assert_trees_equal(base, traced)
        evs_c = []
        with cycle_events(evs_c.append):
            pass
        assert evs_c == []                      # hook uninstalled on exit


def test_trace_cycles_shim_still_works():
    prob = _grid_batch(4, 5, 6, 6)
    calls = []
    with trace_cycles(lambda c, n: calls.append((c, n))):
        maxflow_grid_batch(prob, compact=True)
    assert calls and calls[0][0] == 0 and calls[0][1] == 5
    assert all(isinstance(c, int) and isinstance(n, int) for c, n in calls)
    n_installed = len(calls)
    maxflow_grid_batch(prob, compact=True)
    assert len(calls) == n_installed, "shim hook leaked past its context"


def test_refill_session_bitmatch_and_spans():
    rng = np.random.default_rng(5)
    ws = [rng.integers(0, 50, (5, 5)) for _ in range(6)]
    queue = list(ws[3:])

    def admit(n_free):
        out, queue[:] = queue[:n_free], queue[n_free:]
        return out

    base = RefillSolver("assignment", shape=(5,), capacity=3).run(
        ws[:3], admit=admit)
    queue[:] = list(ws[3:])
    tr = Tracer()
    traced = RefillSolver("assignment", shape=(5,), capacity=3,
                          tracer=tr).run(ws[:3], admit=admit)
    assert set(base) == set(traced) == set(range(6))
    for i in base:
        _assert_trees_equal(base[i], traced[i])
    names = [s.name for s in tr.spans()]
    assert names.count("bucket/pad") == 6       # one intake span per payload
    solve = [s for s in tr.spans() if s.name == "device-solve"]
    assert len(solve) == 1
    assert solve[0].attrs["driver"] == "refill"
    assert solve[0].attrs["kind"] == "assignment"
    assert solve[0].attrs["capacity"] == 3


# --------------------------------------------- serving: lifecycle spans

@pytest.mark.serve
def test_async_lifecycle_reconstructs_every_ticket():
    """The acceptance trace: a refill-enabled async session leaves a full
    submit/queue-wait/solve/resolve chain for every resolved ticket."""
    tr = Tracer()
    probs = _grid_problems(6, 9, 6, 6)
    with use_tracer(tr):
        eng = AsyncSolverEngine(max_batch=4, max_delay_ms=30.0, refill=True)
    assert eng.tracer is tr                     # captured from the ambient var
    with eng:
        futs = [eng.submit("maxflow", p) for p in probs]
        results = [f.result(timeout=WAIT_S) for f in futs]
    assert all(r is not None for r in results)
    chains = _ticket_chains(tr)
    _check_lifecycle(chains, range(len(probs)))
    for t, spans in chains.items():
        for s in spans:
            if s.name == "queue-wait":
                assert s.attrs["trigger"] in {"size", "deadline", "manual",
                                              "drain", "refill"}
            if s.name == "solve":
                assert s.attrs["driver"] in {"masked", "compacted", "refill",
                                             "isolated"}
            assert s.attrs["kind"] == "maxflow"
    other = {s.name for s in tr.spans() if "ticket" not in s.attrs}
    assert {"bucket/pad", "device-solve"} <= other
    # the whole trace exports cleanly
    json.dumps(tr.to_chrome())
    assert prometheus_text(eng.metrics).startswith("# HELP repro_")


@pytest.mark.serve
@multi
def test_async_lifecycle_sharded_two_devices():
    mesh = make_solver_mesh(2)
    tr = Tracer()
    probs = _grid_problems(7, 8, 6, 6)
    with AsyncSolverEngine(max_batch=4, max_delay_ms=30.0, refill=True,
                           mesh=mesh, tracer=tr) as eng:
        futs = [eng.submit("maxflow", p) for p in probs]
        for f in futs:
            assert f.result(timeout=WAIT_S) is not None
    _check_lifecycle(_ticket_chains(tr), range(len(probs)))


def _gated_refill_factory(real_kind, started, gate):
    """Wrap a kind's refill runtime so the FIRST finalize blocks on
    ``gate`` (signalling ``started``) — pinning the session mid-solve so
    requests submitted meanwhile can only resolve via admission (the
    deterministic-admission pattern of tests/test_refill.py)."""
    def factory(**kw):
        rt = real_kind.refill(**kw)

        def finalize(problems, st1, r):
            if not started.is_set():
                started.set()
                assert gate.wait(timeout=WAIT_S), "test gate never opened"
            return rt.finalize(problems, st1, r)

        return rt._replace(finalize=finalize)
    return factory


@pytest.mark.serve
def test_refill_admission_spans(monkeypatch):
    """Mid-solve-admitted tickets trace ``trigger="refill"`` queue-waits,
    refill-driver solve spans, and a ``refill-admission`` span naming
    them."""
    started, gate = threading.Event(), threading.Event()
    real = kinds_mod.get_kind("assignment")
    monkeypatch.setitem(
        kinds_mod._REGISTRY, "assignment",
        real._replace(refill=_gated_refill_factory(real, started, gate)))
    rng = np.random.default_rng(8)
    ws = [rng.integers(0, 50, (5, 5)) for _ in range(4)]
    tr = Tracer()
    with AsyncSolverEngine(max_batch=4, max_delay_ms=LONG_DEADLINE_MS,
                           refill=True, tracer=tr) as eng:
        seed = eng.submit("assignment", ws[0])
        eng.flush_now()                          # open the session
        assert started.wait(timeout=WAIT_S), "session never reached finalize"
        futs = [eng.submit("assignment", w) for w in ws[1:]]
        gate.set()
        assert seed.result(timeout=WAIT_S) is not None
        for f in futs:
            assert f.result(timeout=WAIT_S) is not None
    chains = _ticket_chains(tr)
    _check_lifecycle(chains, range(4))
    admitted = set()
    for t, spans in chains.items():
        for s in spans:
            if s.name == "queue-wait" and s.attrs["trigger"] == "refill":
                admitted.add(t)
            if s.name == "solve" and t != 0:
                assert s.attrs["driver"] == "refill"
    assert admitted == {1, 2, 3}, \
        f"expected tickets 1-3 admitted mid-solve, got {admitted}"
    adm = [s for s in tr.spans() if s.name == "refill-admission"]
    assert adm, "no refill-admission span recorded"
    assert set().union(*(s.attrs["tickets"] for s in adm)) == {1, 2, 3}
    for s in adm:
        assert s.attrs["kind"] == "assignment"
        assert 1 <= s.attrs["admitted"] <= s.attrs["n_free"]


@pytest.mark.serve
def test_async_serving_bitmatch_traced_vs_untraced():
    """Tracing observes the serving path without steering it: the same
    request stream yields identical results with and without a tracer."""
    probs = _grid_problems(9, 6, 6, 6)

    def run(tracer):
        with AsyncSolverEngine(max_batch=3, max_delay_ms=30.0, refill=True,
                               tracer=tracer) as eng:
            futs = [eng.submit("maxflow", p) for p in probs]
            return [f.result(timeout=WAIT_S) for f in futs]

    tr = Tracer()
    for plain, traced in zip(run(None), run(tr)):
        _assert_trees_equal(plain, traced)
    assert tr.spans(), "traced run recorded nothing"


@pytest.mark.serve
def test_instrumented_paths_deprecationwarning_free():
    """The non-shim engine/scheduler paths run clean under
    ``-W error::DeprecationWarning`` even while traced."""
    tr = Tracer()
    probs = _grid_problems(10, 3, 6, 6)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        blocking = SolverEngine(tracer=tr)
        tickets = [blocking.submit("maxflow", p) for p in probs]
        res = blocking.flush()
        assert set(tickets) <= set(res)
        with AsyncSolverEngine(max_batch=3, max_delay_ms=30.0,
                               tracer=tr) as eng:
            futs = [eng.submit("maxflow", p) for p in probs]
            for f in futs:
                assert f.result(timeout=WAIT_S) is not None
        prometheus_text(eng.metrics)
        json.dumps(tr.to_chrome())


# ------------------------------- host stages, the profiler, the device

def _named(spans, name):
    return [s for s in spans if s.name == name]


@pytest.mark.serve
@pytest.mark.parametrize("refill", [False, True])
def test_cache_put_and_validate_spans_close_the_chain(refill):
    """Every resolved ticket has one ``cache/put`` span between the end of
    its ``solve`` and the start of its ``resolve``; a ``validate`` span
    lies inside every ``submit`` on the caller's thread."""
    tr = Tracer()
    probs = _grid_problems(11, 5, 6, 6)
    with AsyncSolverEngine(max_batch=3, max_delay_ms=30.0, refill=refill,
                           tracer=tr) as eng:
        futs = [eng.submit("maxflow", p) for p in probs]
        for f in futs:
            assert f.result(timeout=WAIT_S) is not None
    spans = tr.spans()
    chains = _ticket_chains(tr)
    _check_lifecycle(chains, range(len(probs)))
    validates = _named(spans, "validate")
    assert len(validates) == len(probs)
    for t in range(len(probs)):
        (put,) = _named(chains[t], "cache/put")
        (solve,) = _named(chains[t], "solve")
        (resolve,) = _named(chains[t], "resolve")
        assert solve.t1 <= put.t0 <= put.t1 <= resolve.t0
        assert put.attrs == {"ticket": t, "kind": "maxflow"}
        (sub,) = _named(chains[t], "submit")
        assert any(v.tid == sub.tid and sub.t0 <= v.t0 <= v.t1 <= sub.t1
                   and v.attrs == {"kind": "maxflow"} for v in validates)


def test_blocking_engine_spans_its_host_stages():
    """The blocking engine's stages put the ``repro.core.batch`` spans
    inside its own: ``batch/stage`` in ``bucket/pad``, the three
    ``solve/*`` spans in ``device-solve`` (dispatch, then the wait for
    the device, then the crop), one ``cache/put`` per ticket."""
    tr = Tracer()
    eng = SolverEngine(tracer=tr)
    tickets = [eng.submit("maxflow", p) for p in _grid_problems(12, 3, 6, 6)]
    eng.flush()
    spans = tr.spans()
    by_id = {s.span_id: s for s in spans}
    (pad,) = _named(spans, "bucket/pad")
    (stage,) = _named(spans, "batch/stage")
    assert stage.parent_id == pad.span_id
    (solve,) = _named(spans, "device-solve")
    for name in ("solve/dispatch", "solve/crop", "solve/wait"):
        (s,) = _named(spans, name)
        assert by_id[s.parent_id] is solve
    d, c, w = (_named(spans, n)[0] for n in
               ("solve/dispatch", "solve/crop", "solve/wait"))
    assert d.t1 <= w.t0 and w.t1 <= c.t0
    assert sorted(s.attrs["ticket"] for s in _named(spans, "cache/put")) \
        == tickets
    assert len(_named(spans, "validate")) == len(tickets)


def _host_event_names(trace_dir) -> set:
    from jax.profiler import ProfileData
    (path,) = pathlib.Path(trace_dir).glob("plugins/profile/*/*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return {ev.name for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events}


@pytest.mark.parametrize("kind", ["maxflow", "assignment"])
def test_batch_spans_reach_a_profiler_capture(kind, tmp_path, monkeypatch):
    """With no tracer, one ``solve_batch`` under a ``jax.profiler`` capture
    leaves the host-stage spans on the capture's host plane; with no
    capture and no tracer, no tracer span is opened at all."""
    from repro.core import solve_batch
    rng = np.random.default_rng(13)
    payloads = (_grid_problems(13, 2, 6, 6) if kind == "maxflow" else
                [rng.integers(0, 9, (5, 5)) for _ in range(2)])
    plain = solve_batch(kind, payloads)         # compiles outside the capture

    def no_tracer_span(*a, **k):
        raise AssertionError("a tracer span opened with no tracer installed")
    monkeypatch.setattr(Tracer, "span", no_tracer_span)
    monkeypatch.setattr(Tracer, "record", no_tracer_span)
    assert current_tracer() is None
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        traced = solve_batch(kind, payloads)
    names = _host_event_names(tmp_path)
    assert {"batch/stage", "solve/dispatch", "solve/crop",
            "solve/wait"} <= names
    untraced = solve_batch(kind, payloads)      # no capture, no tracer
    for a, b, c in zip(plain, traced, untraced):
        _assert_trees_equal(a, b)
        _assert_trees_equal(a, c)


def test_span_without_tracer_or_capture_is_the_shared_noop():
    from repro.obs import span
    assert current_tracer() is None
    assert span("a") is span("b", ticket=1)
    assert use_tracer(None) is span("a")        # nothing to install
    tr = Tracer()
    with use_tracer(tr), span("c", ticket=2):
        with span("d"):
            pass
        with use_tracer(None), span("e"):       # an engine with no tracer
            assert current_tracer() is None
    c, = _named(tr.spans(), "c")
    d, = _named(tr.spans(), "d")
    assert c.attrs == {"ticket": 2} and d.parent_id == c.span_id
    assert not _named(tr.spans(), "e")
    assert current_tracer() is None


def test_refill_device_solve_reaches_a_profiler_capture(tmp_path):
    """A refill session with no tracer still puts ``device-solve`` on a
    running capture's host plane, like the engines' own stages."""
    ws = [np.random.default_rng(15).integers(0, 50, (5, 5))
          for _ in range(3)]
    plain = RefillSolver("assignment", shape=(5,), capacity=3).run(ws)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        traced = RefillSolver("assignment", shape=(5,), capacity=3).run(ws)
    assert "device-solve" in _host_event_names(tmp_path)
    for i in plain:
        _assert_trees_equal(plain[i], traced[i])


@pytest.mark.serve
@pytest.mark.parametrize("refill", [False, True])
def test_latency_counts_the_cache_put_before_resolution(refill):
    """A request's exported latency covers the cache puts it waits behind
    before its future resolves (its own and its batch's earlier ones)."""
    from repro.core.warm import SolutionCache
    put_s = 0.3

    class SlowCache(SolutionCache):
        def put(self, *a, **k):
            time.sleep(put_s)
            return super().put(*a, **k)

    probs = _grid_problems(16, 2, 6, 6)
    for cache in (None, SlowCache()):           # the first run compiles
        with AsyncSolverEngine(max_batch=2, max_delay_ms=LONG_DEADLINE_MS,
                               refill=refill, cache=cache) as eng:
            futs = [eng.submit("maxflow", p) for p in probs]
            for f in futs:
                assert f.result(timeout=WAIT_S) is not None
            lat = eng.metrics.snapshot()["latency_ms"]
    assert lat["p50"] >= put_s * 1e3 * 1.5      # one and two puts
    assert lat["p99"] >= put_s * 1e3 * 1.98


def _fresh_program():
    """A jitted program no cache holds: its constant is new every call."""
    c = float(time.monotonic_ns() % 1_000_003)
    return jax.jit(lambda x: x * c + 1.0)(jnp.arange(7.0)).block_until_ready()


@pytest.mark.serve
def test_compile_spans_while_an_engine_holds_a_tracer():
    """A fresh jit under an open traced engine leaves a ``compile`` span;
    after ``close`` the listener is gone and compiles leave nothing."""
    from repro.obs import trace as trace_mod
    tr = Tracer()
    with AsyncSolverEngine(max_batch=2, max_delay_ms=30.0, tracer=tr):
        _fresh_program()
    comp = _named(tr.spans(), "compile")
    assert comp, "no compile span recorded"
    assert all(0 <= s.t1 - s.t0 < 600 for s in comp)
    assert all(s.attrs["event"] in trace_mod.COMPILE_EVENTS for s in comp)
    n = len(tr.spans())
    _fresh_program()
    assert len(tr.spans()) == n


def test_watch_compiles_records_until_unwatched():
    from repro.obs import watch_compiles
    tr = Tracer()
    unwatch = watch_compiles(tr)
    try:
        _fresh_program()
    finally:
        unwatch()
    (comp,) = _named(tr.spans(), "compile")
    assert comp.tid == threading.get_ident() and comp.t0 <= comp.t1
    _fresh_program()
    assert len(_named(tr.spans(), "compile")) == 1


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_grid_solve_hlo_names_its_stages(backend):
    """The batched grid solve's HLO carries the solver's named scopes in
    its ``op_name`` metadata."""
    prob = _grid_batch(14, 2, 8, 128)
    text = jax.jit(lambda p: maxflow_grid_batch(p, backend=backend)).lower(
        prob).as_text(debug_info=True)
    for scope in ("maxflow/init", "maxflow/push", "maxflow/relabel",
                  "maxflow/finalize"):
        assert scope in text, f"{scope} missing from the lowered HLO"


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("method", ["auction", "pushrelabel"])
def test_assignment_solve_hlo_names_its_stages(method, backend):
    """The batched assignment solve's HLO carries the solver's named scopes
    in its ``op_name`` metadata."""
    w = jnp.asarray(np.random.default_rng(15).integers(0, 101, (2, 8, 8)),
                    jnp.int32)
    text = jax.jit(lambda w: solve_assignment(
        w, method=method, backend=backend)).lower(w).as_text(debug_info=True)
    for scope in ("assignment/init", "assignment/refine",
                  "assignment/price_update", "assignment/rescale",
                  "assignment/finalize"):
        assert scope in text, f"{scope} missing from the lowered HLO"


def _pallas_names(jaxpr) -> list:
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                out += _pallas_names(sub)
    return out


def _bench_match(kernel: str) -> str | None:
    path = (pathlib.Path(__file__).resolve().parents[1] / "bench" / "kernels"
            / f"{kernel}.py")
    if not path.is_file():
        return None
    ns: dict = {}
    exec(path.read_text(), ns)
    return ns["MATCH"]


def _kernel_jaxpr(which):
    from repro.kernels.bfs_relabel.kernel import bfs_relabel_sweeps
    from repro.kernels.bidding.kernel import bidding
    from repro.kernels.frontier.kernel import frontier
    from repro.kernels.grid_push.kernel import (grid_push_decide,
                                                grid_push_decide_sched,
                                                grid_push_round)
    f32, i32 = jnp.float32, jnp.int32
    S = jax.ShapeDtypeStruct
    B, H, W = 1, 8, 128
    plane, planes = S((B, H, W), f32), S((4, B, H, W), f32)
    ih, ihs = S((B, H, W), i32), S((4, B, H, W), i32)
    n = S((), i32)
    if which == "grid_push_decide":
        return jax.make_jaxpr(lambda *a: grid_push_decide(
            *a, interpret=True))(plane, ih, planes, ihs, plane, plane, n)
    if which == "grid_push_decide_sched":
        return jax.make_jaxpr(lambda *a: grid_push_decide_sched(
            *a, block_h=8, block_w=128, interpret=True))(
            plane, ih, planes, ihs, plane, plane, S((B, 1), i32),
            S((B,), i32), n)
    if which == "grid_push_round":
        return jax.make_jaxpr(lambda *a: grid_push_round(
            *a, bh=8, interpret=True))(plane, ih, planes, plane, plane, n)
    if which == "bfs_relabel_sweeps":
        return jax.make_jaxpr(lambda *a: bfs_relabel_sweeps(
            *a, interpret=True))(planes, ih, ih, ih, ih)
    if which == "frontier":
        return jax.make_jaxpr(lambda *a: frontier(*a, interpret=True))(
            S((8, 128), jnp.bool_), S((8,), i32), S((8,), i32))
    return jax.make_jaxpr(lambda *a: bidding(*a, interpret=True))(
        S((8, 128), i32), S((128,), i32), S((8, 128), jnp.bool_))


@pytest.mark.parametrize("kernel,bench_kernel", [
    ("grid_push_decide", "grid_push"),
    ("grid_push_decide_sched", "grid_push"),
    ("grid_push_round", "grid_push"),
    ("bfs_relabel_sweeps", None),
    ("frontier", None),
    ("bidding", "bidding"),
])
def test_pallas_calls_carry_their_names(kernel, bench_kernel):
    """Every ``pallas_call`` is named explicitly, and the name holds the
    substring the benchmark's trace reader matches it by."""
    assert _pallas_names(_kernel_jaxpr(kernel).jaxpr) == [kernel]
    if bench_kernel is not None:
        assert _bench_match(bench_kernel) in kernel


# ----------------------------------------------------- metrics hygiene

def test_latency_window_empty_percentiles_are_none():
    win = LatencyWindow()
    assert win.percentiles() == {"p50": None, "p99": None}
    assert len(win) == 0


def test_latency_window_single_sample_percentiles_coincide():
    win = LatencyWindow()
    win.record(42.0)
    p = win.percentiles()
    assert p["p50"] == p["p99"] == 42.0


def test_ewma_alpha_bounds():
    for alpha in (0.0, -0.25, 1.5):
        with pytest.raises(ValueError, match="alpha"):
            Ewma(alpha=alpha)
    last_only = Ewma(alpha=1.0)                 # boundary: tracks the last x
    last_only.update(3.0)
    last_only.update(7.0)
    assert last_only.value == 7.0
    assert Ewma().value is None


def test_metrics_concurrent_hammer():
    """Racing recorders from many threads lose nothing: every counter
    lands exactly."""
    m = SchedulerMetrics()
    n_threads, n_iter = 8, 200
    barrier = threading.Barrier(n_threads)

    def worker(k):
        barrier.wait()
        for i in range(n_iter):
            m.record_submit(queue_depth=i)
            m.record_flush("size", queue_depth=0)
            m.record_dispatch("maxflow", compact=bool(i % 2), spread=0.1,
                              occupancy=0.5, rounds=4.0, heuristics=1.0)
            m.record_done(1.0)
            m.record_live_trace(i, n_live=2)
            m.record_refill_session("maxflow")
            m.record_refill_admit("maxflow", 2)
            m.record_refill_cycle("maxflow", 0.75)
            m.record_cancelled()

    threads = [threading.Thread(target=worker, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * n_iter
    snap = m.snapshot()
    assert snap["tickets"] == {"submitted": total, "completed": total,
                               "cancelled": total}
    assert snap["flushes_by_trigger"] == {"size": total}
    assert snap["dispatches"] == {"maxflow:masked": total // 2,
                                  "maxflow:compacted": total // 2}
    assert snap["compact_cycles"] == total
    assert snap["compact_live_mean"] == 2.0
    assert snap["refill"]["sessions"] == {"maxflow": total}
    assert snap["refill"]["admitted"] == {"maxflow": 2 * total}
    assert snap["refill"]["utilization"] == pytest.approx(0.75)
    assert snap["latency_ms"]["p50"] == 1.0


def test_snapshot_is_a_deep_copy():
    m = SchedulerMetrics()
    m.record_submit(queue_depth=3)
    m.record_refill_admit("maxflow", 2)
    m.record_dispatch("maxflow", compact=False, spread=0.5, occupancy=1.0)
    snap = m.snapshot()
    snap["tickets"]["submitted"] = 10 ** 6
    snap["refill"]["admitted"]["maxflow"] = -1
    snap["refill"]["sessions"]["injected"] = 99
    snap["spread_ewma"]["maxflow"] = -42.0
    fresh = m.snapshot()
    assert fresh["tickets"]["submitted"] == 1
    assert fresh["refill"]["admitted"] == {"maxflow": 2}
    assert "injected" not in fresh["refill"]["sessions"]
    assert fresh["spread_ewma"]["maxflow"] == 0.5


# ------------------------------------------------- prometheus exposition

# every snapshot key maps to the exposition family its renderer emits; the
# two-way assertion below forces this table (and the renderer registry) to
# grow whenever the snapshot does
FAMILY_OF = {
    "queue_depth": "repro_queue_depth",
    "tickets": "repro_tickets_total",
    "flushes_by_trigger": "repro_flushes_total",
    "dispatches": "repro_dispatches_total",
    "latency_ms": "repro_ticket_latency_ms",
    "latency_samples": "repro_ticket_latency_samples",
    "compact_cycles": "repro_compact_cycles_total",
    "compact_live_mean": "repro_compact_live_mean",
    "refill": "repro_refill_sessions_total",
    "warm": "repro_warm_cache_lookups_total",
    "spread_ewma": "repro_spread_ewma",
    "occupancy_ewma": "repro_occupancy_ewma",
    "rounds_ewma": "repro_rounds_ewma",
    "heuristics_ewma": "repro_heuristics_ewma",
}


def _populated_metrics() -> SchedulerMetrics:
    m = SchedulerMetrics()
    m.record_submit(queue_depth=2)
    m.record_flush("deadline", queue_depth=0)
    m.record_dispatch("maxflow", compact=True, spread=0.3, occupancy=0.9,
                      rounds=7.0, heuristics=2.0)
    m.record_done(12.5)
    m.record_live_trace(0, n_live=4)
    m.record_refill_session("maxflow")
    m.record_refill_admit("maxflow", 3)
    m.record_refill_cycle("maxflow", 0.5)
    m.record_cache_lookup(True)
    m.record_cache_lookup(False)
    m.record_warm("maxflow", 2, 6, rounds_saved=3.0)
    return m


def test_prometheus_renders_every_snapshot_field():
    m = _populated_metrics()
    snap = m.snapshot()
    assert set(snap) == set(FAMILY_OF), (
        "snapshot keys and the exposition-family table diverged — teach "
        "repro.obs.export (and this test) about the new field")
    text = prometheus_text(m)
    for key, family in FAMILY_OF.items():
        assert f"# HELP {family} " in text, f"{key} not rendered"
        assert f"# TYPE {family} " in text
    # spot-check labels and values
    assert 'repro_tickets_total{status="submitted"} 1' in text
    assert 'repro_flushes_total{trigger="deadline"} 1' in text
    assert 'repro_dispatches_total{kind="maxflow",driver="compacted"} 1' \
        in text
    assert 'repro_ticket_latency_ms{quantile="0.5"} 12.5' in text
    assert 'repro_refill_admitted_total{kind="maxflow"} 3' in text
    assert 'repro_warm_cache_lookups_total{result="hit"} 1' in text
    assert 'repro_warm_solves_total{init="warm"} 2' in text
    assert 'repro_warm_fraction 0.25' in text
    assert 'repro_warm_rounds_saved_ewma{kind="maxflow"} 3' in text
    assert text.endswith("\n")


def test_prometheus_accepts_snapshot_dict_and_skips_none():
    text = prometheus_text(SchedulerMetrics().snapshot())
    # empty window / unobserved EWMAs: family headers stay, no samples
    assert "# HELP repro_ticket_latency_ms " in text
    assert "repro_ticket_latency_ms{" not in text
    assert "repro_compact_live_mean\n" not in text.replace("gauge\n", "")
    assert "repro_queue_depth 0" in text


def test_prometheus_unknown_snapshot_key_raises():
    snap = SchedulerMetrics().snapshot()
    snap["brand_new_metric"] = 1
    with pytest.raises(KeyError, match="brand_new_metric"):
        prometheus_text(snap)


# ------------------------------------------------------ bench harness

def _bench_run_module():
    root = pathlib.Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:               # direct-file invocation
        sys.path.insert(0, str(root))
    import benchmarks.run as bench_run
    return bench_run


def _fake_bench(rows, repeats=2):
    eng = SolverEngine()                        # captures the ambient tracer
    adj = np.ones((3, 3), dtype=bool)
    ticket = eng.submit("matching", adj)
    res = eng.flush()[ticket]
    rows.append(("fake_matching", 1.5, int(res.rounds), "card=3"))
    rows.append(("fake_legacy", 2.5, "derived=x"))  # legacy 3-tuple row


def test_bench_wall_column_and_trace(tmp_path, monkeypatch, capsys):
    bench_run = _bench_run_module()
    from repro.core.kinds import registered_kinds
    monkeypatch.setattr(bench_run, "BENCHES", {"fake": _fake_bench})
    monkeypatch.setattr(bench_run, "KIND_BENCHES",
                        {k: "fake" for k in registered_kinds()})
    csv, trace = tmp_path / "bench.csv", tmp_path / "trace.json"
    bench_run.main(["fake", "--csv", str(csv), "--trace", str(trace)])
    out = capsys.readouterr().out
    lines = csv.read_text().splitlines()
    assert lines[0] == "name,us_per_call,rounds,wall_s,derived"
    assert out.splitlines()[0] == lines[0]      # stdout carries the same CSV
    r1 = lines[1].split(",")
    assert r1[0] == "fake_matching" and r1[2] != ""
    assert float(r1[3]) >= 0.0
    r2 = lines[2].split(",")
    assert r2[0] == "fake_legacy" and r2[2] == ""   # rounds stays empty
    assert float(r2[3]) >= 0.0 and r2[4] == "derived=x"
    events = load_trace(trace)
    names = {e["name"] for e in events}
    # the engine built inside the bench captured the ambient tracer
    assert {"bench", "bucket/pad", "device-solve"} <= names
    (bench_ev,) = [e for e in events if e["name"] == "bench"]
    assert bench_ev["args"]["bench"] == "fake"


def test_bench_csv_without_trace_flag(tmp_path, monkeypatch, capsys):
    bench_run = _bench_run_module()
    from repro.core.kinds import registered_kinds
    monkeypatch.setattr(bench_run, "BENCHES", {"fake": _fake_bench})
    monkeypatch.setattr(bench_run, "KIND_BENCHES",
                        {k: "fake" for k in registered_kinds()})
    csv = tmp_path / "bench.csv"
    bench_run.main(["fake", "--csv", str(csv)])
    capsys.readouterr()
    lines = csv.read_text().splitlines()
    assert lines[0] == "name,us_per_call,rounds,wall_s,derived"
    assert len(lines) == 3 and all(len(l.split(",")) == 5
                                   for l in lines[1:])
