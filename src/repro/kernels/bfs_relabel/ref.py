"""Pure-jnp oracles for the bfs_relabel kernels (and their combine steps).

``bfs_relabel_sweeps_ref`` mirrors one kernel invocation (``SWEEPS`` joint
relaxation sweeps); ``bfs_relabel_heights_ref`` is the full bidirectional
fixpoint + combine the ops-level driver must reproduce;
``sink_bfs_heights_ref`` is the sink-only relabel, one grid at a time —
the bit-exact references asserted in tests/test_bfs_relabel.py.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core.maxflow.grid import _nbr_h

# python int, not jnp.int32 (lazy import inside a trace must not create
# jnp constants — they would leak as tracers)
INF_H = 2 ** 30


def _relax(plane, cap, seed):
    """One min-plus sweep of a wavefront plane (batch axes pass through)."""
    out = plane
    for d in range(4):
        out = jnp.minimum(
            out, jnp.where(cap[d] > 0, _nbr_h(plane, d) + 1, INF_H))
    return jnp.minimum(out, seed)


def bfs_relabel_sweeps_ref(cap, seed_t, seed_s, dt, ds, *, sweeps: int):
    """``sweeps`` joint relaxation sweeps — the kernel's per-call contract."""
    for _ in range(sweeps):
        dt, ds = _relax(dt, cap, seed_t), _relax(ds, cap, seed_s)
    return dt, ds


def bfs_relabel_heights_ref(cap, cap_src, cap_sink, h_prev, n_nodes):
    """Fixpoint + combine: the bidirectional global/gap relabel oracle.

    Runs both wavefronts to their exact fixpoints (host-bounded sweep
    count: the grid diameter is a hard cap on BFS depth), then combines:
    sink-reachable nodes take their exact height-to-sink, source-only
    nodes take ``max(h_prev, N + dist_to_source)`` (the return-flow
    gradient the paper's gap relabel flattens to N), doubly-unreached
    nodes take the paper's ``max(h_prev, N)`` (they hold no excess — see
    the flow-decomposition argument in docs/kernels.md).
    """
    import numpy as np
    seed_t = jnp.where(cap_sink > 0, jnp.int32(1), INF_H)
    seed_s = jnp.where(cap_src > 0, jnp.int32(n_nodes) + 1, INF_H)
    dt = seed_t
    ds = seed_s
    while True:  # eager oracle: iterate concrete arrays to the fixpoint
        nt, ns = _relax(dt, cap, seed_t), _relax(ds, cap, seed_s)
        if np.array_equal(np.asarray(nt), np.asarray(dt)) and \
                np.array_equal(np.asarray(ns), np.asarray(ds)):
            break
        dt, ds = nt, ns
    return jnp.where(dt < INF_H, dt,
                     jnp.maximum(h_prev,
                                 jnp.where(ds < INF_H, ds,
                                           jnp.int32(n_nodes))))


def sink_bfs_heights_ref(cap, cap_sink, h_prev, n_nodes, max_iters: int):
    """The sink-only global + gap relabel, one grid at a time.

    Each grid relaxes its sink wavefront (seeded 1 where residual x→t
    exists) until a sweep changes nothing or ``max_iters`` sweeps have
    run, then unreached nodes take ``max(h_prev, N)``. Leading batch axes
    of ``h_prev`` (and of ``cap`` after its direction axis) are grids.
    """
    *batch, H, W = h_prev.shape
    B = int(np.prod(batch, dtype=int))
    cap = jnp.reshape(cap, (4, B, H, W))
    cap_sink = jnp.reshape(cap_sink, (B, H, W))
    h_prev = jnp.reshape(h_prev, (B, H, W))
    out = []
    for b in range(B):
        seed = jnp.where(cap_sink[b] > 0, jnp.int32(1), INF_H)
        h = seed
        for _ in range(max_iters):
            new = _relax(h, cap[:, b], seed)
            if np.array_equal(np.asarray(new), np.asarray(h)):
                break
            h = new
        out.append(jnp.where(h < INF_H, h,
                             jnp.maximum(h_prev[b], jnp.int32(n_nodes))))
    return jnp.stack(out).reshape(tuple(batch) + (H, W))
