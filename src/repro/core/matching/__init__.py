"""Bipartite maximum-cardinality matching — the registry's third kind.

This package is the proof-of-seam for the solver-kind registry
(``repro.core.kinds``): a complete new solver — lock-free BFS
augmenting-path matching after Deveci et al. (arXiv:1303.1379), as one
``LoopSpec`` plus a pallas frontier kernel — rides the ragged pad-and-
bucket front end, pow2 bucketing, mesh sharding, early-exit compaction,
and the async serving engine with ZERO changes to those layers, purely by
registering itself here.  See docs/solvers.md for the add-a-kind
walkthrough this package follows.

NOTE: unlike the other solver subpackages this one has a real
``__init__`` on purpose — importing ``repro.core.matching`` is what
registers the ``"matching"`` kind, and the registry's lazy builtin import
relies on that side effect.

Payload forms accepted by the validator (both canonicalize to a dense
``(nl, nr)`` bool numpy adjacency):

  * a dense 2-D bool or 0/1 array — ``adj[i, j]`` iff left ``i`` ~ right
    ``j``;
  * an ``(edges, (nl, nr))`` tuple, ``edges`` an ``(E, 2)`` integer array
    of ``(left, right)`` endpoint ids.
"""
from __future__ import annotations

from typing import Iterable

import jax.numpy as jnp
import numpy as np

from repro.core.batch import (BucketStats, PreparedBucket, _make_buckets,
                              _stats)
from repro.core.kinds import SolverKind, register_kind
from repro.core.matching.bfs import (MatchingResult, match_bipartite,
                                     match_bipartite_batch, matching_ops,
                                     pad_matching_problem)
from repro.core.matching.ref import hopcroft_karp
from repro.obs.trace import span

__all__ = [
    "MatchingResult", "match_bipartite", "match_bipartite_batch",
    "hopcroft_karp", "validate_matching_problem", "pad_matching_problem",
    "inert_matching_problem", "prepare_matching_buckets",
    "solve_prepared_matching",
]


def validate_matching_problem(payload) -> np.ndarray:
    """Canonicalize + validate a matching request (the kind's validator).

    Same reject-before-ticket contract as the other kinds: malformed
    requests raise ``ValueError`` before any queue entry or future exists.
    Accepts a dense bool / 0-1 adjacency or an ``(edges, (nl, nr))``
    tuple; returns the dense ``(nl, nr)`` bool adjacency.
    """
    if (isinstance(payload, tuple) and len(payload) == 2
            and isinstance(payload[1], (tuple, list))
            and len(payload[1]) == 2
            and np.asarray(payload[0]).ndim == 2
            and np.asarray(payload[0]).shape[-1] == 2):
        edges = np.asarray(payload[0])
        nl, nr = (int(s) for s in payload[1])
        if nl < 1 or nr < 1:
            raise ValueError(
                f"malformed matching problem: empty side in shape "
                f"({nl}, {nr})")
        if not np.issubdtype(edges.dtype, np.integer):
            raise ValueError(
                f"malformed matching problem: edge list must hold integer "
                f"vertex ids, got dtype {edges.dtype}")
        if edges.size and edges.min() < 0:
            raise ValueError(
                f"malformed matching problem: negative vertex id "
                f"{int(edges.min())} in edge list")
        if edges.size and (edges[:, 0].max() >= nl
                           or edges[:, 1].max() >= nr):
            raise ValueError(
                f"malformed matching problem: edge endpoint out of range "
                f"for shape ({nl}, {nr})")
        adj = np.zeros((nl, nr), bool)
        adj[edges[:, 0], edges[:, 1]] = True
        return adj
    try:
        a = np.asarray(payload)
    except (TypeError, ValueError) as e:
        raise ValueError(f"malformed matching problem: not array-like ({e})")
    if a.ndim != 2 or a.dtype == object:
        raise ValueError(
            f"malformed matching problem: need a 2-D (nl, nr) adjacency "
            f"or an (edges, (nl, nr)) tuple, got shape {a.shape} dtype "
            f"{a.dtype}")
    if a.shape[0] < 1 or a.shape[1] < 1:
        raise ValueError(
            f"malformed matching problem: empty side in shape {a.shape}")
    if a.dtype != bool:
        if not (np.issubdtype(a.dtype, np.integer)
                or np.issubdtype(a.dtype, np.floating)):
            raise ValueError(
                f"malformed matching problem: non-numeric adjacency dtype "
                f"{a.dtype}")
        if not np.isin(np.asarray(a), (0, 1)).all():
            raise ValueError(
                "malformed matching problem: adjacency entries must be "
                "0/1 (not a bipartite adjacency matrix)")
    return a.astype(bool)


def inert_matching_problem(nl: int, nr: int) -> np.ndarray:
    """An edge-less instance: zero liveness seed, converges in 0 rounds —
    the matching kind's shard-padding filler."""
    return np.zeros((nl, nr), bool)


def prepare_matching_buckets(
    payloads: Iterable,
    *,
    bucket: str = "max",
    mesh=None,
    mesh_axis: str | None = None,
) -> list[PreparedBucket]:
    """HOST stage of the ``"matching"`` kind: bucket, pad, and stack.

    Payloads run through ``validate_matching_problem`` (idempotent for
    already-dense adjacencies), so both the dense and the
    ``(edges, (nl, nr))`` edge-list forms work here exactly as they do at
    engine submit time.
    """
    with span("batch/stage"):
        adjs = [validate_matching_problem(p) for p in payloads]
        shapes = [a.shape for a in adjs]

        def build(bshape, idxs, n_pad):
            NL, NR = bshape
            mats = [pad_matching_problem(adjs[i], NL, NR) for i in idxs]
            mats += [inert_matching_problem(NL, NR)] * n_pad
            return jnp.asarray(np.stack(mats)), None

        return _make_buckets("matching", shapes, bucket=bucket, mesh=mesh,
                             mesh_axis=mesh_axis, build=build)


def solve_prepared_matching(
    prep: PreparedBucket,
    *,
    compact: bool = False,
    mesh=None,
    mesh_axis: str | None = None,
    **solver_kw,
) -> tuple[dict[int, MatchingResult], BucketStats]:
    """DEVICE stage of the ``"matching"`` kind: one batched dispatch.

    Returns ``({request_position: result}, BucketStats)``; ``match_row``
    / ``match_col`` are cropped back to the request's original (nl, nr)
    (padded vertices are isolated, so the crop discards only ``-1``s and
    the cardinality is unchanged).
    """
    with span("solve/dispatch"):
        res = match_bipartite_batch(prep.stacked, compact=compact, mesh=mesh,
                                    mesh_axis=mesh_axis, **solver_kw)
    with span("solve/wait"):
        stats = _stats("matching", prep, res.rounds, res.converged, compact)
    with span("solve/crop"):
        crop = matching_ops(**solver_kw).crop
        out = {i: crop(res, b, prep.shapes[b])
               for b, i in enumerate(prep.idxs)}
    return out, stats


def _matching_inert(shape: tuple) -> np.ndarray:
    return inert_matching_problem(*shape)


def _matching_warm_state(*, max_rounds: int = 10_000,
                         greedy_init: bool = True, backend: str = "xla"):
    """Warm per-instance init: seed the state with the prior matched pairs
    that survive the mutated adjacency and let the augmenting phases
    restore maximality (``repro.core.matching.bfs._match_warm``)."""
    from repro.core.matching.bfs import _match_warm_jit

    def warm1(stacked1, solution, *, base_problem1=None, delta_bound=None):
        adj = jnp.asarray(stacked1, jnp.bool_)
        mr = jnp.asarray(solution["match_row"], jnp.int32)
        mr = jnp.pad(mr, (0, adj.shape[-2] - mr.shape[-1]),
                     constant_values=-1)[None]
        return _match_warm_jit(adj, mr, greedy_init=greedy_init)

    return warm1


def _matching_solution_of(res: MatchingResult):
    """Cacheable artifact: the matched forest's row side (the column side
    is rebuilt from it at warm time)."""
    return {"match_row": res.match_row}


register_kind(SolverKind(
    name="matching",
    validate=validate_matching_problem,
    inert_problem=_matching_inert,
    prepare_buckets=prepare_matching_buckets,
    solve_prepared=solve_prepared_matching,
    ops=matching_ops,
    warm_state=_matching_warm_state,
    solution_of=_matching_solution_of,
))
