"""Batched multi-instance solver engine: pad-and-bucket front end.

The paper's solvers are throughput devices — the CUDA implementations
amortize kernel-launch cost over thousands of nodes; this module amortizes
*dispatch* cost over many instances. ``solve_batch(kind, payloads)`` takes
a ragged collection of problems of one registered solver kind
(``repro.core.kinds``), pads each to a bucket shape (value-preserving,
see the per-kind pad helpers), stacks every bucket into one leading batch
axis, and runs ONE jitted dispatch per bucket. The historical per-kind
entry points — ``solve_maxflow_batch`` / ``solve_assignment_batch`` — are
thin wrappers over the same generic path.

Per-instance convergence inside a batch is handled by the solvers' liveness
masks: a converged instance is frozen via selects while the rest keep
iterating, so batched results bit-match a Python loop of single-instance
solves of the same padded problems (asserted in tests/test_batch.py).

Bucketing contract (``bucket=``):
  * ``"max"``  — every instance pads to the global max shape: one dispatch.
  * ``"pow2"`` — shapes round up to powers of two: a few dispatches, bounded
    padding waste (< 4x area for grids, < 2x for matrices).
  * ``"exact"``— no padding: one dispatch per distinct shape.
Results are always returned in input order, cropped back to original sizes.

Sharding (``mesh=``): pass a ``jax.sharding.Mesh``
(``repro.launch.mesh.make_solver_mesh``) and each bucket's batch axis is
partitioned across the mesh under ``shard_map``. Buckets whose size is not a
multiple of the shard count are padded with INERT instances (each kind's
``inert_problem`` — an instance that converges immediately and cannot
perturb batch-mates) that are dropped before returning — so ragged queues
of any size shard cleanly, and results still bit-match the unsharded path
(tests/test_shard.py). See docs/batching.md for the full semantics.

Two-stage split (the serving scheduler's pipeline hook): each solve front
end is the composition of a HOST stage and a DEVICE stage —

  * ``prepare_buckets(kind, payloads)`` — pure host work (bucketing,
    padding, stacking) producing ``PreparedBucket``s;
  * ``solve_prepared(prep)`` — the jitted dispatch plus result cropping,
    returning per-request results AND a ``BucketStats`` record (batch
    occupancy, per-instance round spread, convergence counts).

``repro.serve.scheduler`` overlaps the host stage of batch *k+1* with the
device stage of batch *k* and feeds the stats into its adaptive
masked-vs-compacted dispatch policy; the blocking front ends below expose
the same stats through ``stats_out=``.

Both stages are spanned (``repro.obs.span``: the ambient tracer, and a
running ``jax.profiler`` capture): ``batch/stage`` around each kind's host
stage, and, in order, ``solve/dispatch`` (the jitted call returning),
``solve/wait`` (the stats read, which blocks until the device is done)
and ``solve/crop`` (one jitted crop per instance, on the idle device)
inside its device stage.

This module also REGISTERS the paper's two kinds (``"maxflow"`` and
``"assignment"``) with the solver-kind registry at the bottom of the file;
the third kind, ``"matching"``, registers itself in
``repro.core.matching`` — see docs/solvers.md for the walkthrough of
adding a kind.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Iterable, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.assignment.cost_scaling import (AssignmentResult,
                                               solve_assignment)
from repro.core.kinds import SolverKind, get_kind, register_kind
from repro.core.maxflow.grid import (GridFlowResult, GridProblem,
                                     maxflow_grid_batch)
from repro.core.refill import RefillRuntime
from repro.obs.trace import span

__all__ = [
    "pad_grid_problem", "stack_grid_problems", "pad_cost_matrix",
    "inert_grid_problem", "inert_cost_matrix", "solve_maxflow_batch",
    "solve_assignment_batch", "PreparedBucket", "BucketStats",
    "prepare_buckets", "solve_prepared", "solve_batch",
    "prepare_maxflow_buckets", "solve_prepared_maxflow",
    "prepare_assignment_buckets", "solve_prepared_assignment",
    "validate_grid_problem", "validate_assignment_matrix",
]


def _pow2(x: int) -> int:
    return 1 << max(0, int(x) - 1).bit_length() if x > 1 else 1


def _bucket_shape(shape: tuple, mode: str, max_shape: tuple) -> tuple:
    if mode == "max":
        return max_shape
    if mode == "pow2":
        return tuple(_pow2(s) for s in shape)
    if mode == "exact":
        return shape
    raise ValueError(f"unknown bucket mode: {mode!r}")


def _shard_pad(n_real: int, mesh, mesh_axis) -> int:
    """Inert instances to append so the bucket batch splits evenly on mesh."""
    if mesh is None:
        return 0
    from repro.launch.mesh import shard_count
    return -n_real % shard_count(mesh, mesh_axis)


class PreparedBucket(NamedTuple):
    """One bucket's host-stage output: padded, stacked, dispatch-ready.

    ``kind`` names the registered solver kind (``repro.core.kinds``) whose
    ``solve_prepared`` consumes this bucket — the registry, not this
    module, is the source of truth for which kinds exist
    (``registered_kinds()``). ``idxs`` are positions in the original
    request sequence (results from the device stage are keyed by them);
    ``shapes`` are the requests' original shapes for cropping;
    ``originals`` holds raw per-request payloads when a kind's device
    stage needs unpadded values (the assignment kind recomputes weights on
    them) and is ``None`` otherwise. ``n_pad`` counts trailing inert
    instances appended for mesh-shard divisibility — the stacked batch is
    ``len(idxs) + n_pad`` instances, reals first.
    """

    kind: str                    # a registered solver kind name
    shape: tuple                 # bucket shape, e.g. (H, W) / (m,) / (nl, nr)
    idxs: tuple[int, ...]        # request positions, in submission order
    shapes: tuple                # original per-request shapes
    stacked: Any                 # batch-leading stacked problem pytree
    originals: tuple | None      # raw payloads, when the kind needs them
    n_pad: int                   # trailing inert shard-padding instances


class BucketStats(NamedTuple):
    """What one batched dispatch observed — the adaptive-dispatch signal.

    ``kind`` is the registered solver kind the bucket was dispatched
    through. ``spread`` is the normalized per-instance round raggedness
    ``(rounds_max - rounds_min) / max(rounds_max, 1)`` over REAL instances:
    ~0 when the whole bucket converges together (masked dispatch is
    optimal), toward 1 when stragglers dominate (early-exit compaction
    pays — see benchmarks/RESULTS_compaction.md).

    ``heur_min``/``heur_max``/``heur_mean`` summarize per-instance
    heuristic (global-relabel) invocations for kinds that report them
    (``"maxflow"``); ``None`` for kinds that don't. Under
    ``backend="balanced"`` the relabel cadence is stall-driven, so this is
    the knob-tuning signal: heur_mean ≈ rounds_mean / rounds_per_heuristic
    means the stall trigger degenerated to the fixed cadence.
    """

    kind: str
    shape: tuple
    n_real: int
    n_pad: int
    compact: bool
    rounds_min: int
    rounds_max: int
    rounds_mean: float
    n_converged: int
    heur_min: int | None = None
    heur_max: int | None = None
    heur_mean: float | None = None

    @property
    def spread(self) -> float:
        return (self.rounds_max - self.rounds_min) / max(self.rounds_max, 1)


def _stats(kind: str, prep: PreparedBucket, rounds, converged,
           compact: bool, heuristics=None) -> BucketStats:
    r = np.asarray(rounds)[:len(prep.idxs)]          # real instances only
    c = np.asarray(converged)[:len(prep.idxs)]
    heur: dict = {}
    if heuristics is not None:
        hh = np.asarray(heuristics)[:len(prep.idxs)]
        heur = dict(heur_min=int(hh.min()), heur_max=int(hh.max()),
                    heur_mean=float(hh.mean()))
    return BucketStats(
        kind=kind, shape=prep.shape, n_real=len(prep.idxs),
        n_pad=prep.n_pad, compact=compact,
        rounds_min=int(r.min()), rounds_max=int(r.max()),
        rounds_mean=float(r.mean()), n_converged=int(c.sum()), **heur)


def _make_buckets(kind: str, shapes: Sequence[tuple], *, bucket: str,
                  mesh, mesh_axis,
                  build: Callable) -> list[PreparedBucket]:
    """The shared host-stage loop every kind's ``prepare_buckets`` drives.

    Groups request positions by bucket shape (per-axis max under
    ``"max"``, per-axis pow2 under ``"pow2"``, identity under
    ``"exact"``), computes the inert shard padding, and calls
    ``build(bucket_shape, idxs, n_pad) -> (stacked, originals)`` for the
    kind-specific pad/stack work.
    """
    if not shapes:
        return []
    ndim = len(shapes[0])
    max_shape = tuple(max(s[d] for s in shapes) for d in range(ndim))
    groups: dict[tuple, list[int]] = {}
    for i, s in enumerate(shapes):
        groups.setdefault(_bucket_shape(s, bucket, max_shape), []).append(i)
    out = []
    for bshape, idxs in groups.items():
        n_pad = _shard_pad(len(idxs), mesh, mesh_axis)
        stacked, originals = build(bshape, idxs, n_pad)
        out.append(PreparedBucket(
            kind=kind, shape=bshape, idxs=tuple(idxs),
            shapes=tuple(shapes[i] for i in idxs), stacked=stacked,
            originals=originals, n_pad=n_pad))
    return out


# ------------------------------------------------- generic (registry) API

def prepare_buckets(kind: str, payloads: Sequence, *, bucket: str = "max",
                    mesh=None,
                    mesh_axis: str | None = None) -> list[PreparedBucket]:
    """HOST stage for any registered kind: bucket, pad, and stack a ragged
    queue of ``kind`` payloads (dispatches to the kind's registration —
    unknown kinds raise ``ValueError`` naming the registered ones)."""
    return get_kind(kind).prepare_buckets(payloads, bucket=bucket,
                                          mesh=mesh, mesh_axis=mesh_axis)


def solve_prepared(prep: PreparedBucket, *, compact: bool = False,
                   mesh=None, mesh_axis: str | None = None,
                   **solver_kw) -> tuple[dict[int, Any], BucketStats]:
    """DEVICE stage for any registered kind: one batched dispatch of a
    prepared bucket, routed through ``prep.kind``'s registration. Returns
    ``({payload_position: result}, BucketStats)``."""
    return get_kind(prep.kind).solve_prepared(
        prep, compact=compact, mesh=mesh, mesh_axis=mesh_axis, **solver_kw)


def solve_batch(
    kind: str,
    payloads: Iterable,
    *,
    bucket: str = "max",
    compact: bool = False,
    mesh=None,
    mesh_axis: str | None = None,
    stats_out: list | None = None,
    warm: dict | None = None,
    **solver_kw,
) -> list:
    """Solve many (possibly ragged) instances of one registered kind.

    The generic front end every kind rides: ``prepare_buckets`` +
    ``solve_prepared`` composed back-to-back, one jitted dispatch per
    bucket, results in input order cropped back to original shapes.

    Args:
      kind: a registered solver kind name (``registered_kinds()``);
        unknown kinds raise ``ValueError`` naming the registered ones.
      payloads: the kind's problem instances (any mix of shapes).
      bucket: ``"max"`` | ``"pow2"`` | ``"exact"`` — see the module
        docstring / docs/batching.md for the dispatch-count vs
        padding-waste trade-off.
      compact: early-exit compaction per bucket (``repro.core.solver_loop``;
        results bit-match the masked default, see docs/batching.md).
      mesh / mesh_axis: optional device mesh — each bucket's batch axis is
        sharded across it, padded with the kind's inert instances so every
        bucket splits evenly (dropped before returning).
      stats_out: optional list; one ``BucketStats`` per dispatched bucket
        is appended (occupancy + round-spread telemetry for the serving
        scheduler's adaptive dispatch).
      warm: optional ``{payload_position: repro.core.warm.WarmStart}`` —
        those instances are warm-started from their cached prior solutions
        through the kind's ``warm_state`` hook, mixed into the same
        buckets as the cold instances (``repro.core.warm.solve_warm``
        drives the dispatch; docs/warmstart.md).
      **solver_kw: forwarded to the kind's solver (``backend=``,
        ``max_rounds=``, ...).
    """
    payloads = list(payloads)
    k = get_kind(kind)
    if not payloads:
        return []
    if warm:
        from repro.core.warm import solve_warm
        return solve_warm(kind, payloads, warm, bucket=bucket,
                          compact=compact, mesh=mesh, mesh_axis=mesh_axis,
                          stats_out=stats_out, **solver_kw)
    results: list = [None] * len(payloads)
    for prep in k.prepare_buckets(payloads, bucket=bucket, mesh=mesh,
                                  mesh_axis=mesh_axis):
        out, stats = k.solve_prepared(prep, compact=compact, mesh=mesh,
                                      mesh_axis=mesh_axis, **solver_kw)
        if stats_out is not None:
            stats_out.append(stats)
        for i, r in out.items():
            results[i] = r
    return results


# ---------------------------------------------------------------- max-flow

def validate_grid_problem(problem) -> GridProblem:
    """Canonicalize + validate a max-flow request (shapes, dtypes, values).

    The ``"maxflow"`` kind's registered validator — the submit-time
    contract shared by ``SolverEngine`` and ``AsyncSolverEngine``:
    malformed requests are rejected BEFORE a ticket or future exists, so a
    queue can never hold an entry that would wedge a batched flush. Checks
    shape ((4, H, W) / (H, W) / (H, W)), numeric dtype (bool and object
    arrays are refused), and values — capacities must be finite and
    non-negative (a negative or NaN capacity breaks the residual-graph
    invariants silently rather than loudly).
    """
    try:
        cap, cs, ct = (jnp.asarray(a) for a in problem)
    except (TypeError, ValueError) as e:
        raise ValueError(f"malformed grid problem: not array-like ({e})")
    if cap.ndim != 3 or cap.shape[0] != 4 or cs.shape != ct.shape \
            or cs.shape != cap.shape[1:]:
        raise ValueError(
            f"malformed grid problem: cap_nbr {cap.shape}, "
            f"cap_src {cs.shape}, cap_sink {ct.shape}; expected "
            f"(4, H, W) / (H, W) / (H, W)")
    for name, a in (("cap_nbr", cap), ("cap_src", cs), ("cap_sink", ct)):
        if not (jnp.issubdtype(a.dtype, jnp.floating)
                or jnp.issubdtype(a.dtype, jnp.integer)):
            raise ValueError(
                f"malformed grid problem: {name} has non-numeric dtype "
                f"{a.dtype} (need integer or floating capacities)")
        v = np.asarray(a)
        if not np.all(np.isfinite(v)):
            raise ValueError(
                f"malformed grid problem: {name} contains non-finite "
                f"capacities (NaN/inf)")
        if np.any(v < 0):
            raise ValueError(
                f"malformed grid problem: {name} contains negative "
                f"capacities (min={v.min()})")
    return GridProblem(cap, cs, ct)


def pad_grid_problem(problem: GridProblem, H: int, W: int) -> GridProblem:
    """Zero-capacity pad a grid-cut instance to (H, W).

    Padded nodes carry no terminal or neighbour capacity, so they hold no
    excess and never push or relabel usefully — they are inert, and the
    max-flow value (and the cut restricted to the original window) of the
    padded instance equals the original's.
    """
    cap, cs, ct = problem
    h, w = cs.shape[-2:]
    assert H >= h and W >= w, (H, W, h, w)
    pad2 = ((0, H - h), (0, W - w))
    return GridProblem(
        cap_nbr=jnp.pad(cap, ((0, 0),) + pad2),
        cap_src=jnp.pad(cs, pad2),
        cap_sink=jnp.pad(ct, pad2),
    )


def stack_grid_problems(problems: Sequence[GridProblem]) -> GridProblem:
    """Stack same-shape instances into the (B, 4, H, W) batched layout."""
    return GridProblem(
        cap_nbr=jnp.stack([jnp.asarray(p.cap_nbr) for p in problems]),
        cap_src=jnp.stack([jnp.asarray(p.cap_src) for p in problems]),
        cap_sink=jnp.stack([jnp.asarray(p.cap_sink) for p in problems]),
    )


def inert_grid_problem(H: int, W: int) -> GridProblem:
    """An all-zero-capacity instance: no excess, converges in 0 rounds.

    Used to pad a bucket's batch to a multiple of the mesh shard count —
    inert instances never push, relabel, or affect their batch-mates (the
    solvers' masks are per instance), so appending them is value-preserving.
    """
    return GridProblem(
        cap_nbr=jnp.zeros((4, H, W), jnp.float32),
        cap_src=jnp.zeros((H, W), jnp.float32),
        cap_sink=jnp.zeros((H, W), jnp.float32),
    )


def prepare_maxflow_buckets(
    problems: Iterable[GridProblem],
    *,
    bucket: str = "max",
    mesh=None,
    mesh_axis: str | None = None,
) -> list[PreparedBucket]:
    """HOST stage of the ``"maxflow"`` kind: bucket, pad, and stack.

    Pure host/numpy + stacking work, no solver dispatch — this is the stage
    the async scheduler overlaps with the previous batch's device solve.
    Returns one ``PreparedBucket`` per distinct bucket shape, each already
    padded with inert instances to the mesh's shard count (if any).
    """
    with span("batch/stage"):
        problems = [GridProblem(*(jnp.asarray(a) for a in p))
                    for p in problems]
        shapes = [tuple(p.cap_src.shape) for p in problems]

        def build(bshape, idxs, n_pad):
            H, W = bshape
            padded = [pad_grid_problem(problems[i], H, W) for i in idxs]
            padded += [inert_grid_problem(H, W)] * n_pad
            return stack_grid_problems(padded), None

        return _make_buckets("maxflow", shapes, bucket=bucket, mesh=mesh,
                             mesh_axis=mesh_axis, build=build)


def solve_prepared_maxflow(
    prep: PreparedBucket,
    *,
    backend: str = "xla",
    compact: bool = False,
    mesh=None,
    mesh_axis: str | None = None,
    **solver_kw,
) -> tuple[dict[int, GridFlowResult], BucketStats]:
    """DEVICE stage of the ``"maxflow"`` kind: one batched dispatch.

    Returns ``({request_position: result}, BucketStats)`` — results are
    cropped back to each request's original (H, W), exactly as
    ``solve_maxflow_batch`` returns them.
    """
    with span("solve/dispatch"):
        res = maxflow_grid_batch(prep.stacked, backend=backend,
                                 compact=compact, mesh=mesh,
                                 mesh_axis=mesh_axis, **solver_kw)
    with span("solve/wait"):
        stats = _stats("maxflow", prep, res.rounds, res.converged, compact,
                       heuristics=res.heuristics)
    with span("solve/crop"):
        out = {i: _crop_grid(res, b, h=prep.shapes[b][0], w=prep.shapes[b][1])
               for b, i in enumerate(prep.idxs)}
    return out, stats


@functools.partial(jax.jit, static_argnames=("h", "w"))
def _crop_grid(res: GridFlowResult, b, *, h: int, w: int) -> GridFlowResult:
    """Instance ``b`` of a bucket's result, cropped to its (h, w), in one
    dispatch: the device stage crops after the solve, on an idle device,
    where eager slicing would take a dispatch per field."""
    st = res.state
    return GridFlowResult(
        flow=res.flow[b],
        cut=res.cut[b, :h, :w],
        state=st._replace(
            e=st.e[b, :h, :w], h=st.h[b, :h, :w],
            cap=st.cap[b, :, :h, :w],
            cap_src=st.cap_src[b, :h, :w],
            cap_sink=st.cap_sink[b, :h, :w],
            sink_flow=st.sink_flow[b], src_flow=st.src_flow[b],
            heur=None if st.heur is None else st.heur[b]),
        rounds=res.rounds[b],
        converged=res.converged[b],
        heuristics=None if res.heuristics is None else res.heuristics[b],
    )


def solve_maxflow_batch(
    problems: Iterable[GridProblem],
    *,
    bucket: str = "max",
    compact: bool = False,
    mesh=None,
    mesh_axis: str | None = None,
    stats_out: list | None = None,
    **solver_kw,
) -> list[GridFlowResult]:
    """Solve many ragged grid-cut instances — thin wrapper over
    ``solve_batch("maxflow", ...)``; see it for the argument contract.
    ``**solver_kw`` forwards to ``maxflow_grid_batch`` (``backend=``,
    ``max_rounds=``, ...). Returns one ``GridFlowResult`` per instance in
    input order, cropped back to the instance's original (H, W)."""
    return solve_batch("maxflow", problems, bucket=bucket, compact=compact,
                       mesh=mesh, mesh_axis=mesh_axis, stats_out=stats_out,
                       **solver_kw)


# -------------------------------------------------------------- assignment

def validate_assignment_matrix(w) -> np.ndarray:
    """Canonicalize + validate an assignment request (square int matrix).

    The ``"assignment"`` kind's registered validator (same
    reject-before-ticket contract as ``validate_grid_problem``).
    """
    w = np.asarray(w)
    if w.ndim != 2 or w.shape[0] != w.shape[1] \
            or not np.issubdtype(w.dtype, np.integer):
        raise ValueError(
            f"malformed assignment request: need a square integer "
            f"matrix, got shape {w.shape} dtype {w.dtype}")
    return w


def pad_cost_matrix(w, m: int):
    """Pad an (n, n) integer weight matrix to (m, m), optimum-preserving.

    The real block gets a uniform bonus ``1 - min(0, w.min())`` so every
    real-real arc strictly beats the zero-weight dummy arcs: every optimal
    perfect matching of the padded matrix matches real rows to real columns
    (exchange argument — rerouting a real row from a dummy column to any
    real column gains ``w + bonus >= 1``), and the real block's restriction
    is exactly an optimal matching of the original. Padded weight =
    original weight + n * bonus. Caller must keep
    ``m * (m+1) * max|w + bonus|`` inside int32 (same contract as
    ``solve_assignment``).

    Returns ``(padded, bonus)``.
    """
    w = np.asarray(w)
    n = w.shape[-1]
    assert m >= n, (m, n)
    assert np.issubdtype(w.dtype, np.integer), "integer weights only"
    bonus = int(1 - min(0, int(w.min()))) if n else 1
    out = np.zeros((m, m), np.int32)
    out[:n, :n] = w + bonus
    return jnp.asarray(out), bonus


def inert_cost_matrix(m: int) -> jax.Array:
    """A zero-weight (m, m) instance: any perfect matching is optimal, the
    ε schedule collapses to one short ε=1 refine, and other instances never
    observe it — the assignment kind's shard-padding filler."""
    return jnp.zeros((m, m), jnp.int32)


def prepare_assignment_buckets(
    costs: Sequence,
    *,
    bucket: str = "max",
    mesh=None,
    mesh_axis: str | None = None,
) -> list[PreparedBucket]:
    """HOST stage of the ``"assignment"`` kind: bucket, bonus-pad, stack.

    Mirrors ``prepare_maxflow_buckets``; ``originals`` keeps the unpadded
    matrices so the device stage can recompute matching weights on the REAL
    costs (the padded solve runs on bonus-shifted values).
    """
    with span("batch/stage"):
        costs = [np.asarray(w) for w in costs]
        shapes = [(w.shape[-1],) for w in costs]

        def build(bshape, idxs, n_pad):
            (m,) = bshape
            mats = [pad_cost_matrix(costs[i], m)[0] for i in idxs]
            mats += [inert_cost_matrix(m)] * n_pad
            return jnp.stack(mats), tuple(costs[i] for i in idxs)

        return _make_buckets("assignment", shapes, bucket=bucket, mesh=mesh,
                             mesh_axis=mesh_axis, build=build)


def solve_prepared_assignment(
    prep: PreparedBucket,
    *,
    compact: bool = False,
    mesh=None,
    mesh_axis: str | None = None,
    **solver_kw,
) -> tuple[dict[int, AssignmentResult], BucketStats]:
    """DEVICE stage of the ``"assignment"`` kind: one batched dispatch.

    Returns ``({request_position: result}, BucketStats)``; weights are
    recomputed on the ORIGINAL (unpadded) costs, exactly as
    ``solve_assignment_batch`` returns them.
    """
    with span("solve/dispatch"):
        res = solve_assignment(prep.stacked, compact=compact, mesh=mesh,
                               mesh_axis=mesh_axis, **solver_kw)
    with span("solve/wait"):
        stats = _stats("assignment", prep, res.rounds, res.converged,
                       compact)
    with span("solve/crop"):
        out = {i: _crop_assignment(
                   res, b, jnp.asarray(prep.originals[b], jnp.int32),
                   n=prep.shapes[b][0])
               for b, i in enumerate(prep.idxs)}
    return out, stats


@functools.partial(jax.jit, static_argnames=("n",))
def _crop_assignment(res: AssignmentResult, b, original,
                     *, n: int) -> AssignmentResult:
    """Instance ``b`` of a bucket's result, cropped to its n, with the
    weight recomputed on its ``original`` costs, in one dispatch (see
    ``_crop_grid``)."""
    col = res.col_of_row[b, :n]
    valid = col < n              # unconverged rows may hold dummy cols
    picked = jnp.take_along_axis(
        original, jnp.minimum(col, n - 1)[:, None], axis=1)[:, 0]
    return AssignmentResult(
        col_of_row=col, weight=jnp.sum(jnp.where(valid, picked, 0)),
        p_x=res.p_x[b, :n], p_y=res.p_y[b, :n],
        rounds=res.rounds[b], pushes=res.pushes[b],
        relabels=res.relabels[b], converged=res.converged[b],
    )

def solve_assignment_batch(
    costs: Sequence,
    *,
    bucket: str = "max",
    compact: bool = False,
    mesh=None,
    mesh_axis: str | None = None,
    stats_out: list | None = None,
    **solver_kw,
) -> list[AssignmentResult]:
    """Solve many ragged assignment instances — thin wrapper over
    ``solve_batch("assignment", ...)``; see it for the argument contract.
    ``**solver_kw`` forwards to ``solve_assignment`` (``method=``,
    ``max_rounds=``, ``backend=``, ...).

    Same-bucket instances are padded with ``pad_cost_matrix``, stacked to
    (B, m, m), and solved by the batch-polymorphic ``solve_assignment`` in
    one dispatch per bucket. Returns one ``AssignmentResult`` per instance
    in input order: ``col_of_row`` is cropped to the original n (a
    permutation of range(n) when ``converged`` — guaranteed by the
    bonus-shifted padding), ``weight`` is recomputed on the ORIGINAL
    weights, and prices keep the padded solver's values (cropped). If an
    instance did NOT converge (hit ``max_rounds``), rows may still point at
    dummy columns: their col values stay >= n so callers can detect them,
    and they contribute 0 to ``weight`` rather than a clamped arbitrary
    entry.
    """
    return solve_batch("assignment", costs, bucket=bucket, compact=compact,
                       mesh=mesh, mesh_axis=mesh_axis, stats_out=stats_out,
                       **solver_kw)


# --------------------------------------------- registry: the builtin kinds

def _maxflow_inert(shape: tuple) -> GridProblem:
    return inert_grid_problem(*shape)


def _maxflow_loop_spec(*, rounds_per_heuristic: int = 32,
                       max_rounds: int = 100_000, bfs_max_iters: int = 0,
                       backend: str = "xla", stall_threshold: float = 0.05):
    """The grid solver's cached ``LoopSpec`` factory (``maxflow_grid``
    defaults); see ``repro.core.maxflow.grid``."""
    from repro.core.maxflow.grid import _grid_spec
    return _grid_spec(rounds_per_heuristic, max_rounds, bfs_max_iters,
                      backend, stall_threshold)


def _maxflow_refill(*, rounds_per_heuristic: int = 32,
                    max_rounds: int = 100_000, bfs_max_iters: int = 0,
                    backend: str = "xla",
                    stall_threshold: float = 0.05) -> RefillRuntime:
    """The ``"maxflow"`` kind's continuous-batching runtime
    (``repro.core.refill``): the same cached spec / jitted init+finalize
    the compacted batch driver uses, so a refilled instance's trajectory
    bit-matches its closed-batch solve.  Problems use the public
    (B, 4, H, W) layout; init/finalize own the internal direction-axis
    moveaxis exactly as ``_grid_batch_compact`` does."""
    from repro.core.maxflow.grid import (_grid_finalize_jit, _grid_init_jit,
                                         _grid_spec)
    spec = _grid_spec(rounds_per_heuristic, max_rounds, bfs_max_iters,
                      backend, stall_threshold)

    def pad_one(problem: GridProblem, shape) -> GridProblem:
        H, W = shape
        return stack_grid_problems([pad_grid_problem(problem, H, W)])

    def init(stacked: GridProblem):
        return _grid_init_jit(
            jnp.moveaxis(jnp.asarray(stacked.cap_nbr), 1, 0),
            jnp.asarray(stacked.cap_src), jnp.asarray(stacked.cap_sink),
            bfs_max_iters=bfs_max_iters)

    def finalize(stacked, state, rounds) -> GridFlowResult:
        res = _grid_finalize_jit(state, rounds,
                                 bfs_max_iters=bfs_max_iters)
        return res._replace(state=res.state._replace(
            cap=jnp.moveaxis(res.state.cap, 0, 1)))

    def crop(res: GridFlowResult, shape, original) -> GridFlowResult:
        h, w = shape
        st = res.state
        return GridFlowResult(
            flow=res.flow[0], cut=res.cut[0, :h, :w],
            state=st._replace(
                e=st.e[0, :h, :w], h=st.h[0, :h, :w],
                cap=st.cap[0, :, :h, :w], cap_src=st.cap_src[0, :h, :w],
                cap_sink=st.cap_sink[0, :h, :w],
                sink_flow=st.sink_flow[0], src_flow=st.src_flow[0],
                heur=None if st.heur is None else st.heur[0]),
            rounds=res.rounds[0], converged=res.converged[0],
            heuristics=None if res.heuristics is None else res.heuristics[0])

    def shape_of(problem: GridProblem) -> tuple:
        return tuple(np.asarray(jnp.asarray(problem.cap_src)).shape)

    return RefillRuntime(spec=spec, pad_one=pad_one, init=init,
                         finalize=finalize, crop=crop, shape_of=shape_of)


def _assignment_inert(shape: tuple) -> jax.Array:
    return inert_cost_matrix(*shape)


def _assignment_refill(*, method: str = "auction", alpha: int = 10,
                       max_rounds: int = 200_000,
                       rounds_per_heuristic: int = 16,
                       use_price_update: bool = True,
                       use_arc_fixing: bool = True,
                       backend: str = "xla") -> RefillRuntime:
    """The ``"assignment"`` kind's continuous-batching runtime: bonus-
    shifted padding on the way in (``pad_cost_matrix``), weight recomputed
    on the ORIGINAL costs on the way out — exactly the
    ``solve_prepared_assignment`` crop, per instance."""
    from repro.core.assignment.cost_scaling import (_assignment_finalize_jit,
                                                    _assignment_spec,
                                                    _scale_init_jit)
    spec = _assignment_spec(method, alpha, max_rounds, rounds_per_heuristic,
                            use_price_update, use_arc_fixing, backend)

    def pad_one(w, shape):
        (m,) = shape
        return pad_cost_matrix(w, m)[0][None]

    def init(stacked):
        return _scale_init_jit(jnp.asarray(stacked, jnp.int32), alpha=alpha)

    def finalize(stacked, state, rounds) -> AssignmentResult:
        # the solver's own per-instance round/push counters live in the
        # state; the driver-side rounds argument is unused (as in the
        # closed-batch path)
        return _assignment_finalize_jit(jnp.asarray(stacked, jnp.int32),
                                        state.st)

    def crop(res: AssignmentResult, shape, original) -> AssignmentResult:
        (n,) = shape
        col = res.col_of_row[0, :n]
        valid = col < n          # unconverged rows may hold dummy cols
        picked = jnp.take_along_axis(
            jnp.asarray(original, jnp.int32),
            jnp.minimum(col, n - 1)[:, None], axis=1)[:, 0]
        weight = jnp.sum(jnp.where(valid, picked, 0))
        return AssignmentResult(
            col_of_row=col, weight=weight,
            p_x=res.p_x[0, :n], p_y=res.p_y[0, :n],
            rounds=res.rounds[0], pushes=res.pushes[0],
            relabels=res.relabels[0], converged=res.converged[0])

    def shape_of(w) -> tuple:
        return (int(np.asarray(w).shape[-1]),)

    return RefillRuntime(spec=spec, pad_one=pad_one, init=init,
                         finalize=finalize, crop=crop, shape_of=shape_of)


def _assignment_loop_spec(*, method: str = "auction", alpha: int = 10,
                          max_rounds: int = 200_000,
                          rounds_per_heuristic: int = 16,
                          use_price_update: bool = True,
                          use_arc_fixing: bool = True,
                          backend: str = "xla"):
    """The assignment solver's cached ``LoopSpec`` factory
    (``solve_assignment`` defaults); see ``repro.core.assignment``."""
    from repro.core.assignment.cost_scaling import _assignment_spec
    return _assignment_spec(method, alpha, max_rounds, rounds_per_heuristic,
                            use_price_update, use_arc_fixing, backend)


# ------------------------------------------------------ warm-start hooks
# (repro.core.warm drives these; see docs/warmstart.md)


def _pad_trailing(a, shape, fill=0):
    """Zero-pad the trailing ``len(shape)`` axes of ``a`` up to ``shape``."""
    a = jnp.asarray(a)
    tail = a.shape[a.ndim - len(shape):]
    pads = [(0, 0)] * (a.ndim - len(shape)) + [
        (0, t - s) for s, t in zip(tail, shape)]
    return jnp.pad(a, pads, constant_values=fill)


def _maxflow_init_state(**solver_kw):
    """Cold per-instance init for the ``"maxflow"`` kind — the refill
    runtime's init, registered so warm/cold mixing shares one code path."""
    return _maxflow_refill(**solver_kw).init


def _maxflow_warm_state(*, rounds_per_heuristic: int = 32,
                        max_rounds: int = 100_000, bfs_max_iters: int = 0,
                        backend: str = "xla", stall_threshold: float = 0.05):
    """Warm per-instance init: recover the prior flow from the cached
    residuals, clamp/repair it against the mutated capacities, and re-BFS
    the heights (``repro.core.maxflow.grid._grid_warm``).  Without a base
    problem the prior flow is unrecoverable from residuals alone, so the
    hook degrades to the cold init."""
    from repro.core.maxflow.grid import _grid_init_jit, _grid_warm_jit

    def warm1(problem1: GridProblem, solution, *, base_problem1=None,
              delta_bound=None):
        cap = jnp.moveaxis(jnp.asarray(problem1.cap_nbr), 1, 0)
        cs = jnp.asarray(problem1.cap_src)
        ct = jnp.asarray(problem1.cap_sink)
        if base_problem1 is None:
            return _grid_init_jit(cap, cs, ct, bfs_max_iters=bfs_max_iters)
        H, W = cs.shape[-2:]
        bcap = jnp.moveaxis(jnp.asarray(base_problem1.cap_nbr), 1, 0)
        bct = jnp.asarray(base_problem1.cap_sink)
        # cached solution arrays are at the ORIGINAL (h, w); inert padding
        # carries no flow, so zero-extending them to the bucket is exact
        pcap = _pad_trailing(solution["cap"], (H, W))[:, None]
        pct = _pad_trailing(solution["cap_sink"], (H, W))[None]
        return _grid_warm_jit(cap, cs, ct, bcap, bct, pcap, pct,
                              bfs_max_iters=bfs_max_iters)

    return warm1


def _maxflow_solution_of(res: GridFlowResult):
    """Cacheable artifact: the residual capacities (grid + sink edges) —
    with the base problem they reconstruct the full prior flow."""
    return {"cap": res.state.cap, "cap_sink": res.state.cap_sink}


def _assignment_init_state(**solver_kw):
    return _assignment_refill(**solver_kw).init


def _assignment_warm_state(*, method: str = "auction", alpha: int = 10,
                           max_rounds: int = 200_000,
                           rounds_per_heuristic: int = 16,
                           use_price_update: bool = True,
                           use_arc_fixing: bool = True,
                           backend: str = "xla"):
    """Warm per-instance init: re-enter the ε ladder at a delta-bounded
    rung with the prior column prices (``_scale_warm``; unconditionally
    correct for ANY prices — see its docstring).  ``delta_bound`` (max
    |Δw| on the original weights) turns into a scaled-cost bound of
    ``(m+1)·2·Δw`` — the factor 2 covers the bonus shift drifting with
    ``min(w)``; with no bound the ladder re-enters at the cold rung and
    only the prices carry over."""
    from repro.core.assignment.cost_scaling import _scale_warm_jit

    def warm1(stacked1, solution, *, base_problem1=None, delta_bound=None):
        w = jnp.asarray(stacked1, jnp.int32)
        m = int(w.shape[-1])
        p_y = jnp.asarray(solution["p_y"], jnp.int32)
        p_y = jnp.pad(p_y, (0, m - p_y.shape[-1]))[None]
        if delta_bound is None:
            dmax = jnp.full((1,), 2 ** 30, jnp.int32)    # clamps to cold ε
        else:
            dmax = jnp.full(
                (1,), min(2 ** 30, (m + 1) * 2 * int(np.ceil(delta_bound))),
                jnp.int32)
        return _scale_warm_jit(w, p_y, dmax, alpha=alpha)

    return warm1


def _assignment_solution_of(res: AssignmentResult):
    """Cacheable artifact: the column prices (the dual half the warm
    ladder reuses)."""
    return {"p_y": res.p_y}


register_kind(SolverKind(
    name="maxflow",
    validate=validate_grid_problem,
    inert_problem=_maxflow_inert,
    prepare_buckets=prepare_maxflow_buckets,
    solve_prepared=solve_prepared_maxflow,
    loop_spec=_maxflow_loop_spec,
    refill=_maxflow_refill,
    init_state=_maxflow_init_state,
    warm_state=_maxflow_warm_state,
    solution_of=_maxflow_solution_of,
))

register_kind(SolverKind(
    name="assignment",
    validate=validate_assignment_matrix,
    inert_problem=_assignment_inert,
    prepare_buckets=prepare_assignment_buckets,
    solve_prepared=solve_prepared_assignment,
    loop_spec=_assignment_loop_spec,
    refill=_assignment_refill,
    init_state=_assignment_init_state,
    warm_state=_assignment_warm_state,
    solution_of=_assignment_solution_of,
))
