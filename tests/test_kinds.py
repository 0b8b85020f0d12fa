"""Solver-kind registry: error paths, registration rules, deprecation shims.

The contracts under test (repro.core.kinds + the serve layer's shims):

* unknown kinds raise ``ValueError`` NAMING the registered kinds — from
  ``get_kind`` and from every front end that dispatches through it;
* duplicate registration raises (silent overwrite would make dispatch
  order-of-import dependent), as do malformed kind names;
* ``registered_kinds()`` ensures the builtins and preserves registration
  order; ``ensure=False`` peeks without importing solver modules;
* each builtin kind's ``ops`` factory hands back ONE cached object per
  set of knobs, and its batched solves go through the named entry points
  and loop drivers that faults and profiles are planted on;
* the pre-registry serve spellings — ``SolverEngine(maxflow_kw=,
  assignment_kw=)``, ``submit_maxflow`` / ``submit_assignment`` on both
  engines — still work but emit ``DeprecationWarning`` and delegate to the
  generic ``solver_kw`` / ``submit(kind, ...)`` path.
"""
import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.assignment.cost_scaling as cost_scaling
import repro.core.batch as batch_mod
import repro.core.kinds as kinds_mod
import repro.core.maxflow.grid as grid
import repro.launch.mesh as mesh_mod
from repro.core.batch import solve_batch
from repro.core.kinds import (SolverKind, get_kind, register_kind,
                              registered_kinds)
from repro.core.maxflow.grid import GridProblem
from repro.core.maxflow.ref import random_grid_problem
from repro.serve.engine import SolverEngine


def _prob(rng, h=5, w=5):
    return GridProblem(*map(jnp.asarray, random_grid_problem(rng, h, w)))


def _dummy_kind(name):
    f = lambda *a, **k: None  # noqa: E731
    return SolverKind(name=name, validate=f, inert_problem=f,
                      prepare_buckets=f, solve_prepared=f, ops=f)


# ------------------------------------------------------------ error paths

def test_unknown_kind_names_registered_kinds():
    with pytest.raises(ValueError) as ei:
        get_kind("tsp")
    msg = str(ei.value)
    assert "unknown solver kind 'tsp'" in msg
    for name in ("maxflow", "assignment", "matching"):
        assert name in msg


def test_unknown_kind_raises_from_every_front_end():
    with pytest.raises(ValueError, match="registered kinds"):
        solve_batch("tsp", [object()])
    with pytest.raises(ValueError, match="registered kinds"):
        SolverEngine().submit("tsp", object())
    from repro.core.batch import prepare_buckets
    with pytest.raises(ValueError, match="registered kinds"):
        prepare_buckets("tsp", [object()])


def test_duplicate_registration_raises(monkeypatch):
    registered_kinds()                     # ensure builtins are present
    with pytest.raises(ValueError, match="already registered"):
        register_kind(_dummy_kind("matching"))
    # and a scratch name registers exactly once
    monkeypatch.delitem(kinds_mod._REGISTRY, "scratch", raising=False)
    register_kind(_dummy_kind("scratch"))
    try:
        with pytest.raises(ValueError, match="already registered"):
            register_kind(_dummy_kind("scratch"))
        assert "scratch" in registered_kinds()
    finally:
        del kinds_mod._REGISTRY["scratch"]


def test_malformed_kind_name_raises():
    with pytest.raises(ValueError, match="non-empty string"):
        register_kind(_dummy_kind(""))
    with pytest.raises(ValueError, match="non-empty string"):
        register_kind(_dummy_kind(None))


def test_registered_kinds_order_and_peek():
    ks = registered_kinds()
    assert ks.index("maxflow") < ks.index("assignment") < ks.index(
        "matching")
    # peek mode never shrinks the view once the builtins are in
    assert set(registered_kinds(ensure=False)) == set(ks)
    assert get_kind("maxflow").name == "maxflow"


# ---------------------------------------------- instance ops and seams

@pytest.mark.parametrize("kind", ["maxflow", "assignment", "matching"])
def test_ops_factory_is_cached(kind):
    """Equal knobs, however spelled, give the SAME ops object — so every
    batch, refill session and warm start shares one set of compiled
    programs — and other knobs another."""
    k = get_kind(kind)
    defaults = {n: p.default
                for n, p in inspect.signature(k.ops).parameters.items()}
    assert k.ops(max_rounds=7) is k.ops(max_rounds=7)
    assert k.ops(**defaults) is k.ops()
    assert k.ops(max_rounds=7) is not k.ops()
    assert k.ops(max_rounds=7).spec is not k.ops().spec


def _counting(orig, calls):
    def wrapped(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)
    return wrapped


@pytest.mark.parametrize("kind,module,name", [
    ("maxflow", grid, "run_masked"),
    ("assignment", cost_scaling, "run_masked"),
    ("maxflow", batch_mod, "maxflow_grid_batch"),
    ("assignment", batch_mod, "solve_assignment"),
    ("maxflow", mesh_mod, "dispatch_sharded"),
])
def test_batched_solve_goes_through_named_seams(kind, module, name,
                                                monkeypatch):
    """``solve_batch`` reaches the kind's loop and entry point through these
    module globals, looked up when the program is traced or called: a
    wrapper planted there by name sees the solve, which still answers
    right."""
    rng = np.random.default_rng(3)
    if kind == "maxflow":
        payloads = [_prob(rng) for _ in range(2)]
    else:
        payloads = [rng.integers(0, 20, (5, 5)) for _ in range(2)]
    want = solve_batch(kind, payloads)
    mesh = None
    if name == "dispatch_sharded":
        from repro.launch.mesh import make_solver_mesh
        mesh = make_solver_mesh(1)
    calls = []
    jax.clear_caches()               # a planted name must be traced anew
    try:
        monkeypatch.setattr(module, name,
                            _counting(getattr(module, name), calls))
        got = solve_batch(kind, payloads, mesh=mesh)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert calls, f"{module.__name__}.{name} was not on the solve's path"
    for g, w in zip(got, want):
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------------ deprecation shims

def test_engine_deprecated_solver_kwargs_map_to_solver_kw():
    with pytest.warns(DeprecationWarning, match="maxflow_kw"):
        eng = SolverEngine(maxflow_kw={"backend": "xla"})
    assert eng.solver_kw == {"maxflow": {"backend": "xla"}}
    with pytest.warns(DeprecationWarning, match="assignment_kw"):
        eng = SolverEngine(solver_kw={"matching": {"max_rounds": 5}},
                           assignment_kw={"alpha": 4})
    assert eng.solver_kw == {"matching": {"max_rounds": 5},
                             "assignment": {"alpha": 4}}


def test_engine_deprecated_submit_shims_delegate():
    rng = np.random.default_rng(0)
    eng = SolverEngine()
    with pytest.warns(DeprecationWarning, match="submit_maxflow"):
        t0 = eng.submit_maxflow(_prob(rng))
    with pytest.warns(DeprecationWarning, match="submit_assignment"):
        t1 = eng.submit_assignment(rng.integers(0, 9, (4, 4)))
    out = eng.flush()
    assert sorted(out) == [t0, t1]
    assert bool(out[t0].converged) and bool(out[t1].converged)
    # the shims still validate (delegation, not a bypass)
    with pytest.warns(DeprecationWarning):
        with pytest.raises(ValueError, match="malformed assignment"):
            eng.submit_assignment(np.ones((3, 4)))


@pytest.mark.serve
def test_async_engine_deprecated_shims_delegate():
    from repro.serve.scheduler import AsyncSolverEngine
    rng = np.random.default_rng(1)
    with pytest.warns(DeprecationWarning, match="maxflow_kw"):
        eng = AsyncSolverEngine(max_batch=2, max_delay_ms=600_000.0,
                                maxflow_kw={"backend": "xla"})
    with eng:
        with pytest.warns(DeprecationWarning, match="submit_maxflow"):
            f0 = eng.submit_maxflow(_prob(rng))
        with pytest.warns(DeprecationWarning, match="submit_assignment"):
            f1 = eng.submit_assignment(rng.integers(0, 9, (4, 4)))
        eng.flush_now()
        assert bool(f0.result(timeout=120.0).converged)
        assert bool(f1.result(timeout=120.0).converged)
