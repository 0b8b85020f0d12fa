"""Import-surface smoke test: repro.core exports the public solver API.

Guards the package façade (`src/repro/core/__init__.py`): every name in
``__all__`` resolves, the names are the SAME objects as their home modules'
(no shadow copies that could drift), and a tiny end-to-end solve works when
driven purely through ``repro.core``.
"""
import jax.numpy as jnp
import numpy as np

import repro.core as core


def test_all_names_resolve():
    missing = [n for n in core.__all__ if not hasattr(core, n)]
    assert not missing, f"__all__ names missing from repro.core: {missing}"
    assert sorted(core.__all__) == list(core.__all__), "__all__ not sorted"


def test_exports_are_home_module_objects():
    from repro.core.assignment import cost_scaling
    from repro.core import batch, kinds, masking, matching, solver_loop
    from repro.core.matching import bfs
    from repro.core.maxflow import grid
    assert core.maxflow_grid is grid.maxflow_grid
    assert core.maxflow_grid_batch is grid.maxflow_grid_batch
    assert core.GridProblem is grid.GridProblem
    assert core.solve_assignment is cost_scaling.solve_assignment
    assert core.solve_maxflow_batch is batch.solve_maxflow_batch
    assert core.solve_assignment_batch is batch.solve_assignment_batch
    assert core.solve_batch is batch.solve_batch
    assert core.prepare_buckets is batch.prepare_buckets
    assert core.solve_prepared is batch.solve_prepared
    assert core.PreparedBucket is batch.PreparedBucket
    assert core.SolverKind is kinds.SolverKind
    assert core.register_kind is kinds.register_kind
    assert core.get_kind is kinds.get_kind
    assert core.registered_kinds is kinds.registered_kinds
    assert core.match_bipartite is bfs.match_bipartite
    assert core.match_bipartite_batch is bfs.match_bipartite_batch
    assert core.MatchingResult is bfs.MatchingResult
    assert core.match_bipartite is matching.match_bipartite
    assert core.freeze is masking.freeze
    assert core.LoopSpec is solver_loop.LoopSpec
    assert core.run_masked is solver_loop.run_masked
    assert core.run_compacted is solver_loop.run_compacted


def test_registered_kinds_exported_and_complete():
    ks = core.registered_kinds()
    assert {"maxflow", "assignment", "matching"} <= set(ks)
    for k in ks:
        kind = core.get_kind(k)
        assert kind.name == k
        assert callable(kind.validate) and callable(kind.prepare_buckets)
        assert callable(kind.solve_prepared) and callable(kind.ops)
        assert callable(kind.inert_problem)


def test_facade_end_to_end_smoke():
    w = np.asarray([[3, 1], [2, 4]])
    res = core.solve_assignment(jnp.asarray(w))
    assert bool(res.converged) and int(res.weight) == 7
    [r] = core.solve_assignment_batch([w], compact=True)
    assert int(r.weight) == 7
    adj = np.eye(3, dtype=bool)
    m = core.match_bipartite(adj)
    assert int(m.cardinality) == 3 and bool(m.converged)
    [mb] = core.solve_batch("matching", [adj])
    assert int(mb.cardinality) == 3
