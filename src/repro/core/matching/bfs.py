"""Bipartite maximum-cardinality matching via lock-free BFS phases — on TPU.

The repo's THIRD solver kind, and the registry's proof-of-seam (ROADMAP:
"a new kind should be ~one ``LoopSpec`` + kernels").  Adapted from the
GPU augmenting-path matching of Deveci, Kaya, Uçar & Çatalyürek
(arXiv:1303.1379): each phase grows an alternating-BFS forest from every
unmatched row simultaneously, columns are claimed lock-free, and one
vertex-disjoint augmenting path per tree is flipped.  Their CUDA kernels
resolve column claims with atomics — thread order decides the winner; here
the claim is a deterministic keyed minimum (smallest root label, then
smallest row index), so a phase is a pure function of the instance and
results bit-match across every batching/sharding/compaction layout.

One heuristic cycle = one phase:

1. FOREST — fixpoint of frontier expansion: labeled rows reach columns
   over non-matching edges (``repro.kernels.frontier`` under
   ``backend="pallas"``; a masked keyed-min reduction under ``"xla"``);
   a newly claimed column records its claiming row as parent and, if
   matched, labels its matched row with the same root.  Claims are
   permanent within a phase — merging trees never shrinks the REACHABLE
   set, so a free column is labeled iff an augmenting path exists (Berge).
2. AUGMENT — each root selects its minimum labeled free column as the one
   endpoint of its tree; the walks back along parent pointers are vertex-
   disjoint (vertices carry exactly one root label, one endpoint per
   root), so every path flips simultaneously with collision-free scatters.
3. LIVENESS — ``progress`` records whether the phase augmented AND a free
   row with edges remains; a phase that finds no endpoint certifies
   maximality (no augmenting path exists), which is the convergence flag.

Everything is shape-polymorphic over leading batch axes and PER-INSTANCE
PURE (the fixpoint/walk ``while_loop`` predicates are shared across the
batch but extra iterations are exact no-ops), so the solver plugs into the
unified runtime of ``repro.core.solver_loop`` unchanged: masked iteration,
early-exit compaction (``compact=True``), and mesh sharding all bit-match
a loop of single-instance solves (tests/test_matching.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.solver_loop import KindOps, LoopSpec, run_masked, solve

INF = jnp.int32(2 ** 30)


class MatchingResult(NamedTuple):
    match_row: jax.Array    # (..., nl) int32: matched col per row, -1 = free
    match_col: jax.Array    # (..., nr) int32: matched row per col, -1 = free
    cardinality: jax.Array  # (...,) int32 matching size
    rounds: jax.Array       # (...,) BFS phases executed per instance
    converged: jax.Array    # (...,) bool: True = maximum certified (Berge)


class MatchState(NamedTuple):
    """Per-instance solver carry (all leaves lead with the batch axes)."""

    adj: jax.Array        # (..., nl, nr) bool adjacency (constant)
    match_row: jax.Array  # (..., nl) int32
    match_col: jax.Array  # (..., nr) int32
    progress: jax.Array   # (...,) bool: an augmenting path may still exist


def _has_free_work(adj, match_row):
    """A free row with at least one edge remains (necessary for any
    augmenting path — every augmenting path starts at such a row)."""
    return jnp.any((match_row < 0) & jnp.any(adj, axis=-1), axis=-1)


def _greedy_match(adj, match_row, match_col):
    """Deterministic maximal greedy matching (the phase-0 init of Deveci
    et al.): free rows propose their minimum free column; each column
    accepts its minimum proposer; repeat to fixpoint.  Per-instance pure —
    the shared ``changed`` predicate only adds exact no-op iterations."""
    *_, nl, nr = adj.shape
    rows_i = jnp.arange(nl, dtype=jnp.int32)
    cols_i = jnp.arange(nr, dtype=jnp.int32)

    def body(carry):
        mr, mc, _ = carry
        free_r = (mr < 0)[..., :, None]
        free_c = (mc < 0)[..., None, :]
        prop = jnp.min(jnp.where(adj & free_r & free_c, cols_i, INF),
                       axis=-1)                          # (..., nl) col | INF
        # each proposed column accepts its minimum proposing row
        bids = jnp.where(prop[..., :, None] == cols_i,
                         rows_i[:, None], INF)           # (..., nl, nr)
        acc = jnp.min(bids, axis=-2)                     # (..., nr) row | INF
        won = (prop < INF) & (jnp.take_along_axis(
            acc, jnp.minimum(prop, nr - 1), axis=-1) == rows_i)
        mr = jnp.where(won, prop, mr)
        mc = jnp.where(acc < INF, acc, mc)
        return mr, mc, jnp.any(won)

    mr, mc, _ = jax.lax.while_loop(
        lambda c: c[2], body, (match_row, match_col, jnp.bool_(True)))
    return mr, mc


def _expand(adj, root_row, match_row, backend: str):
    """One frontier sweep: per column, (min root, claiming row) over labeled
    rows adjacent via non-matching edges — the kernel's contract."""
    if backend == "pallas":
        from repro.kernels.frontier.ops import frontier_op
        op = frontier_op
        for _ in range(adj.ndim - 2):  # one vmap per leading batch axis
            op = jax.vmap(op)
        return op(adj, root_row, match_row)
    *_, nl, nr = adj.shape
    cols_i = jnp.arange(nr, dtype=jnp.int32)
    rows2d = jax.lax.broadcasted_iota(jnp.int32, (nl, nr), 0)
    cand = jnp.where(
        adj & (root_row[..., :, None] < INF)
        & (match_row[..., :, None] != cols_i),
        root_row[..., :, None], INF)
    min_root = jnp.min(cand, axis=-2)
    claim = jnp.min(jnp.where(cand == min_root[..., None, :], rows2d, INF),
                    axis=-2)
    return min_root, claim


def _phase(state: MatchState, backend: str) -> MatchState:
    """One lock-free BFS augmenting-path phase (the LoopSpec cycle)."""
    adj, match_row, match_col, _ = state
    *_, nl, nr = adj.shape
    rows_i = jnp.arange(nl, dtype=jnp.int32)
    cols_i = jnp.arange(nr, dtype=jnp.int32)
    batch = match_row.shape[:-1]

    # ---- 1. alternating-BFS forest from every free row ------------------
    root_row0 = jnp.where(match_row < 0, rows_i, INF)          # (..., nl)
    root_col0 = jnp.full(batch + (nr,), INF)
    parent0 = jnp.zeros(batch + (nr,), jnp.int32)

    def bfs_body(carry):
        root_row, root_col, parent, _ = carry
        min_root, claim = _expand(adj, root_row, match_row, backend)
        newly = (root_col >= INF) & (min_root < INF)
        root_col = jnp.where(newly, min_root, root_col)
        parent = jnp.where(newly, claim, parent)
        # a labeled column's matched row inherits its root label
        rc = jnp.take_along_axis(root_col, jnp.maximum(match_row, 0),
                                 axis=-1)                      # (..., nl)
        row_new = (match_row >= 0) & (root_row >= INF) & (rc < INF)
        root_row = jnp.where(row_new, rc, root_row)
        return (root_row, root_col, parent,
                jnp.any(newly) | jnp.any(row_new))

    root_row, root_col, parent, _ = jax.lax.while_loop(
        lambda c: c[3], bfs_body,
        (root_row0, root_col0, parent0, jnp.bool_(True)))

    # ---- 2. one endpoint per tree, then flip all paths at once ----------
    free_lab = (match_col < 0) & (root_col < INF)              # (..., nr)
    owned = free_lab[..., None, :] & (root_col[..., None, :]
                                      == rows_i[..., :, None])  # (nl, nr)
    endpoint = jnp.min(jnp.where(owned, cols_i, INF), axis=-1)  # (..., nl)
    found = endpoint < INF
    cur0 = jnp.where(found, endpoint, -1)

    def walk_body(carry):
        mr, mc, cur = carry
        active = cur >= 0
        row = jnp.take_along_axis(parent, jnp.maximum(cur, 0), axis=-1)
        prev = jnp.take_along_axis(match_row, jnp.maximum(row, 0), axis=-1)
        # paths are vertex-disjoint: at most one walker writes each slot,
        # so a masked keyed min IS the scatter
        row_hit = active[..., :, None] & (rows_i == row[..., :, None])
        col_for_row = jnp.min(
            jnp.where(row_hit, cur[..., :, None], INF), axis=-2)
        mr = jnp.where(col_for_row < INF, col_for_row, mr)
        col_hit = active[..., :, None] & (cols_i == cur[..., :, None])
        row_for_col = jnp.min(
            jnp.where(col_hit, row[..., :, None], INF), axis=-2)
        mc = jnp.where(row_for_col < INF, row_for_col, mc)
        # step back over the matched edge; a free (root) row ends the walk
        return mr, mc, jnp.where(active, prev, cur)

    match_row, match_col, _ = jax.lax.while_loop(
        lambda c: jnp.any(c[2] >= 0), walk_body,
        (match_row, match_col, cur0))

    # ---- 3. liveness: augmented AND something left to try ---------------
    progress = jnp.any(found, axis=-1) & _has_free_work(adj, match_row)
    return MatchState(adj=adj, match_row=match_row, match_col=match_col,
                      progress=progress)


@functools.lru_cache(maxsize=None)
def _matching_spec(max_rounds: int, backend: str) -> LoopSpec:
    """The matching solver's registration with the solver-loop runtime.

    Cached per static-knob tuple so repeated solves hand the runtime the
    SAME spec object and the compacted drivers' jitted cycles cache-hit.
    One cycle = one BFS augmenting-path phase; the cycle is shape-
    polymorphic, so one spec serves every (nl, nr) and every compaction
    sub-batch size.
    """

    def cycle(state: MatchState) -> MatchState:
        return _phase(state, backend)

    def live(state: MatchState, rounds: jax.Array) -> jax.Array:
        return state.progress & (rounds < max_rounds)

    return LoopSpec(cycle=cycle, live=live, rounds_per_cycle=1,
                    lead_axes_fn=None)


def _match_init(adj, *, greedy_init: bool) -> MatchState:
    """Initial state: optional maximal greedy matching, then the liveness
    seed — a phase can only help while a free row with edges exists (an
    all-isolated or perfectly matched instance converges in 0 rounds)."""
    adj = jnp.asarray(adj, jnp.bool_)
    *batch, nl, nr = adj.shape
    mr = jnp.full(tuple(batch) + (nl,), -1, jnp.int32)
    mc = jnp.full(tuple(batch) + (nr,), -1, jnp.int32)
    if greedy_init:
        mr, mc = _greedy_match(adj, mr, mc)
    return MatchState(adj=adj, match_row=mr, match_col=mc,
                      progress=_has_free_work(adj, mr))


def _match_warm(adj, mr_prior, *, greedy_init: bool) -> MatchState:
    """Warm state: keep the prior matched pairs that survive the new
    adjacency, then let the unchanged augmenting phases restore maximality.

    Any valid matching is a sound starting forest for BFS augmentation
    (Berge: a matching is maximum iff no augmenting path exists — the
    phases find and apply exactly those paths), and maximum CARDINALITY is
    unique, so a warm solve lands on the same optimum as a cold one.  The
    prior pairs are scrubbed against the new adjacency (an edge deleted by
    the delta unmatches both endpoints) and re-checked for mutual
    consistency, so even a stale or foreign cache entry degrades to a
    smaller-but-valid seed rather than an invalid state.  ``greedy_init``
    additionally extends the seed with the phase-0 greedy pass (it only
    proposes free-row/free-col pairs, so the kept pairs are untouched).
    """
    adj = jnp.asarray(adj, jnp.bool_)
    *batch, nl, nr = adj.shape
    rows_i = jnp.arange(nl, dtype=jnp.int32)
    cols_i = jnp.arange(nr, dtype=jnp.int32)
    mr = jnp.asarray(mr_prior, jnp.int32)
    # a pair survives only if its edge still exists
    edge = jnp.take_along_axis(
        adj, jnp.maximum(mr, 0)[..., :, None], axis=-1)[..., 0]
    mr = jnp.where((mr >= 0) & (mr < nr) & edge, mr, -1)
    # rebuild the column side from the row side (mutual consistency even if
    # the cached pair list was inconsistent); ties keep the minimum row
    hit = mr[..., :, None] == cols_i
    mc = jnp.min(jnp.where(hit, rows_i[..., :, None], INF), axis=-2)
    mc = jnp.where(mc < INF, mc, -1)
    # and scrub rows that lost the tie so (mr, mc) is a matching
    back = jnp.take_along_axis(mc, jnp.maximum(mr, 0), axis=-1)
    mr = jnp.where((mr >= 0) & (back == rows_i), mr, -1)
    if greedy_init:
        mr, mc = _greedy_match(adj, mr, mc)
    return MatchState(adj=adj, match_row=mr, match_col=mc,
                      progress=_has_free_work(adj, mr))


_match_warm_jit = jax.jit(_match_warm, static_argnames=("greedy_init",))


def _match_finalize(state: MatchState, rounds) -> MatchingResult:
    """Result view: ``converged`` is the Berge certificate — the last phase
    found no augmenting path (False only when ``max_rounds`` was hit)."""
    return MatchingResult(
        match_row=state.match_row, match_col=state.match_col,
        cardinality=jnp.sum(state.match_row >= 0, axis=-1),
        rounds=rounds, converged=~state.progress)


def matching_ops(*, max_rounds: int = 10_000, greedy_init: bool = True,
                 backend: str = "xla") -> KindOps:
    """The ``"matching"`` kind's instance operations (``KindOps``) for one
    set of ``match_bipartite`` knobs; the same object for equal knobs."""
    return _matching_ops(max_rounds, greedy_init, backend)


@functools.lru_cache(maxsize=None)
def _matching_ops(max_rounds: int, greedy_init: bool, backend: str) -> KindOps:
    spec = _matching_spec(max_rounds, backend)

    def init(adj) -> MatchState:
        return _match_init(adj, greedy_init=greedy_init)

    def finalize(adj, state: MatchState, rounds) -> MatchingResult:
        return _match_finalize(state, rounds)

    def solve(adj) -> MatchingResult:
        state, rounds = run_masked(spec, init(adj), jnp.shape(adj)[:-2])
        return finalize(adj, state, rounds)

    return KindOps(spec=spec, init=jax.jit(init), finalize=jax.jit(finalize),
                   solve=jax.jit(solve), pad_one=_pad_one,
                   shape_of=lambda adj: tuple(np.shape(adj)), crop=_crop)


def pad_matching_problem(adj, NL: int, NR: int) -> np.ndarray:
    """Pad an adjacency with edge-less vertices to (NL, NR) —
    value-preserving: isolated vertices join no matching."""
    adj = np.asarray(adj, bool)
    nl, nr = adj.shape
    assert NL >= nl and NR >= nr, (NL, NR, nl, nr)
    return np.pad(adj, ((0, NL - nl), (0, NR - nr)))


def _pad_one(adj, shape):
    return jnp.asarray(pad_matching_problem(adj, *shape))[None]


@functools.partial(jax.jit, static_argnames=("shape",))
def _crop(res: MatchingResult, b, shape, original=None) -> MatchingResult:
    """Instance ``b`` of a batched result, cropped to its original
    (nl, nr), in one dispatch."""
    nl, nr = shape
    return MatchingResult(
        match_row=res.match_row[b, :nl],
        match_col=res.match_col[b, :nr],
        cardinality=res.cardinality[b],
        rounds=res.rounds[b],
        converged=res.converged[b],
    )


@functools.partial(jax.jit,
                   static_argnames=("max_rounds", "greedy_init", "backend"))
def match_bipartite(
    adj: jax.Array,
    *,
    max_rounds: int = 10_000,
    greedy_init: bool = True,
    backend: str = "xla",
) -> MatchingResult:
    """Maximum-cardinality matching of ONE bipartite instance.

    Args:
      adj: ``(nl, nr)`` bool adjacency — ``adj[i, j]`` iff left vertex
        ``i`` is adjacent to right vertex ``j`` (rectangular fine).
      max_rounds: BFS-phase cap; each phase augments every tree that can
        augment, so at most ``min(nl, nr)`` phases are ever needed — the
        cap exists for parity with the other kinds' ``max_rounds`` knob.
      greedy_init: start from a deterministic maximal greedy matching
        (fewer phases; identical final cardinality either way).
      backend: ``"xla"`` or ``"pallas"`` (the frontier-expansion sweep as
        a TPU kernel, ``repro.kernels.frontier``) — bit-identical results.

    Returns:
      ``MatchingResult``: ``match_row (nl,)`` / ``match_col (nr,)`` with
      ``-1`` marking unmatched vertices, the matching ``cardinality``
      (equal to Hopcroft–Karp's, ``repro.core.matching.ref``), ``rounds``
      (phases run), and ``converged`` (True = maximality certified by a
      phase that found no augmenting path — Berge's theorem).
    """
    if adj.ndim != 2:
        raise ValueError(
            f"match_bipartite solves ONE instance (adj (nl, nr), got "
            f"{adj.shape}); use match_bipartite_batch for stacked problems")
    state = _match_init(adj, greedy_init=greedy_init)
    state, rounds = run_masked(_matching_spec(max_rounds, backend), state, ())
    return _match_finalize(state, rounds)


def match_bipartite_batch(
    adj: jax.Array,
    *,
    max_rounds: int = 10_000,
    greedy_init: bool = True,
    backend: str = "xla",
    compact: bool = False,
    mesh=None,
    mesh_axis: str | None = None,
) -> MatchingResult:
    """Matching on a BATCH of same-shape bipartite instances, one dispatch.

    Args:
      adj: ``(B, nl, nr)`` bool — a plain stack of single-instance
        adjacencies (the pad-and-bucket front end for ragged shapes is
        ``repro.core.batch.solve_batch("matching", ...)``).
      max_rounds / greedy_init / backend: as in ``match_bipartite``
        (applied per instance).
      compact: early-exit compaction (``repro.core.solver_loop``) — an
        instance whose maximality is certified leaves the working set
        between jitted cycle segments instead of being select-masked until
        the batch's slowest instance finishes.  With ``mesh=``, compaction
        stays within each shard's lane (``repro.launch.mesh.compact_lanes``).
      mesh / mesh_axis: optional device mesh — the batch axis is
        partitioned under ``shard_map`` with no collectives; ``B`` must
        divide the shard count (the front end pads with inert all-False
        instances instead of raising).

    Returns ``MatchingResult`` with every leaf leading with the batch axis.

    Bit-match contract: the phase cycle is per-instance pure, so batched
    == a loop of solo solves == sharded == compacted, exactly as for the
    other two kinds (tests/test_matching.py).
    """
    if adj.ndim != 3:
        raise ValueError(
            f"match_bipartite_batch expects adj (B, nl, nr), got "
            f"{adj.shape}; use match_bipartite for a single instance")
    ops = matching_ops(max_rounds=max_rounds, greedy_init=greedy_init,
                       backend=backend)
    return solve(ops, adj, adj.shape[:1], compact=compact, mesh=mesh,
                 mesh_axis=mesh_axis)
