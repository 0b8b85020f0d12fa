"""Incremental re-solve: graph deltas, a solution cache, and the generic
warm-start driver over the SolverKind warm seam.

Production graphs mutate and re-ask (ROADMAP: streaming video cuts, live
marketplace matching, road networks); "Scalable Maxflow Processing for
Dynamic Graphs" (arXiv 2511.01235) shows restarting push-relabel from the
previous preflow/labels after capacity deltas beats from-scratch solves by
large factors, and Baumstark et al. (arXiv 1507.01926) show a valid height
function is the only invariant the restart needs.  This module is the
kind-agnostic half of that pipeline:

* ``GraphDelta`` — a sparse edit (set-semantics) against a validated
  payload: ``apply_delta`` materializes the mutated payload and
  re-validates it, so a delta can never smuggle a malformed problem past
  the submit-time contract.
* ``WarmStart`` — what a warm instance carries into a solve: the cached
  prior ``solution`` (the kind's ``solution_of`` artifact), optionally the
  ``base_problem`` it solved (kinds that reconstruct flows from residuals
  need it) and a precomputed ``delta_bound``.
* ``SolutionCache`` — content-hash keyed (graph identity = bytes of the
  validated payload, not object identity), LRU with entry- and byte-
  budgets; evicted entries spill through ``repro.checkpoint.store.put`` /
  ``get`` and are transparently reloaded on hit.
* ``solve_warm`` — the generic driver: pads warm and cold instances into
  the SAME buckets, builds per-instance states through the kind's
  ``ops.init`` / ``warm_state`` hooks, and drives the UNCHANGED masked /
  compacted / sharded-lane loop runtimes from that state
  (``repro.core.solver_loop.solve``).  The correctness
  contract (tests/test_warm.py): a warm-started solve reaches the same
  optimum as a cold solve of the mutated graph, for every kind and driver.
"""
from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.kinds import get_kind
from repro.core.solver_loop import solve

__all__ = [
    "GraphDelta", "apply_delta", "WarmStart", "SolutionCache",
    "content_key", "delta_bound", "solve_warm",
]


class GraphDelta(NamedTuple):
    """One sparse edit against a validated payload (SET semantics).

    ``field`` selects a payload component by attribute name for structured
    payloads (``"cap_nbr"`` / ``"cap_src"`` / ``"cap_sink"`` on a maxflow
    ``GridProblem``); ``None`` addresses the payload itself when it is a
    single array (the assignment weight matrix, the dense matching
    adjacency).  ``idx`` is a tuple of integer index arrays, one per axis
    of the addressed array (numpy advanced indexing); ``values`` are the
    new entries written at those positions.  Deltas never change shape —
    a warm re-solve is the same graph with different capacities/weights.
    """

    idx: tuple
    values: Any
    field: str | None = None


def apply_delta(kind: str, payload, delta) -> Any:
    """Apply one ``GraphDelta`` (or a sequence) to ``payload``; returns the
    mutated, RE-VALIDATED payload.  The input payload is never aliased."""
    k = get_kind(kind)
    out = k.validate(payload)
    deltas = [delta] if isinstance(delta, GraphDelta) else list(delta)
    for d in deltas:
        if not isinstance(d, GraphDelta):
            raise TypeError(f"expected GraphDelta, got {type(d).__name__}")
        if d.field is None:
            arr = np.array(out, copy=True)
            arr[tuple(np.asarray(i) for i in d.idx)] = d.values
            out = arr
        else:
            if not hasattr(out, d.field):
                raise ValueError(
                    f"{kind!r} payload has no field {d.field!r} "
                    f"(fields: {getattr(out, '_fields', ())})")
            arr = np.array(getattr(out, d.field), copy=True)
            arr[tuple(np.asarray(i) for i in d.idx)] = d.values
            out = out._replace(**{d.field: arr})
    return k.validate(out)


class WarmStart(NamedTuple):
    """Warm-start directive for one instance (see module docstring).

    ``delta_bound`` — an upper bound on the largest per-entry change
    between ``base_problem`` and the instance's (mutated) payload; kinds
    use it to pick how much of their schedule the warm start may skip
    (the assignment ε ladder).  ``None`` means "compute it from
    ``base_problem``, or be conservative".
    """

    solution: Any
    base_problem: Any = None
    delta_bound: float | None = None


def content_key(kind: str, payload) -> str:
    """Content-hash graph identity of a VALIDATED payload.

    Two payloads with equal leaf bytes (dtype, shape, values) get the same
    key regardless of object identity or array backend — the cache key for
    ``SolutionCache`` and the spill key for ``checkpoint.store.put``.
    """
    h = hashlib.sha256(kind.encode())
    for leaf in jax.tree.leaves(payload):
        a = np.ascontiguousarray(np.asarray(jax.device_get(leaf)))
        h.update(str(a.dtype).encode())
        h.update(repr(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def delta_bound(new_payload, base_payload) -> float:
    """Max per-entry absolute change between two same-shape payloads."""
    bound = 0.0
    new_leaves = jax.tree.leaves(new_payload)
    base_leaves = jax.tree.leaves(base_payload)
    if len(new_leaves) != len(base_leaves):
        raise ValueError("payloads differ in structure; no delta bound")
    for n, b in zip(new_leaves, base_leaves):
        na = np.asarray(jax.device_get(n)).astype(np.float64)
        ba = np.asarray(jax.device_get(b)).astype(np.float64)
        if na.shape != ba.shape:
            raise ValueError(
                f"payload leaves differ in shape ({na.shape} vs {ba.shape}); "
                f"deltas never change shape")
        if na.size:
            bound = max(bound, float(np.max(np.abs(na - ba))))
    return bound


class _Entry(NamedTuple):
    kind: str
    problem: Any      # the validated payload the solution solves
    solution: Any     # the kind's solution_of artifact
    nbytes: int


def _tree_nbytes(tree) -> int:
    return int(sum(np.asarray(jax.device_get(l)).nbytes
                   for l in jax.tree.leaves(tree)))


class SolutionCache:
    """LRU solution cache keyed by content-hash graph identity.

    Budgets: at most ``max_entries`` entries and ``max_bytes`` total leaf
    bytes in memory; the least-recently-used entries beyond either budget
    are dropped — or, with ``spill_dir``, persisted through
    ``repro.checkpoint.store.put`` and transparently reloaded (and
    re-promoted to memory) when hit again.  ``hits``/``misses`` count
    ``get`` outcomes; the serving metrics surface reads them.
    """

    def __init__(self, *, max_entries: int = 128,
                 max_bytes: int = 64 << 20, spill_dir: str | None = None):
        if max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        self.max_entries = int(max_entries)
        self.max_bytes = int(max_bytes)
        self.spill_dir = spill_dir
        self._mem: OrderedDict[str, _Entry] = OrderedDict()
        # spilled entries keep their STRUCTURE here (treedefs aren't
        # serializable); leaves live on disk under the same key
        self._spilled: dict[str, tuple] = {}
        # serving drives one shared cache from several lane threads
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._mem) + len(self._spilled)

    @property
    def nbytes(self) -> int:
        with self._lock:
            return sum(e.nbytes for e in self._mem.values())

    def key(self, kind: str, payload) -> str:
        return content_key(kind, get_kind(kind).validate(payload))

    def put(self, kind: str, payload, solution) -> str:
        """Cache ``solution`` for the validated ``payload``; returns key."""
        problem = get_kind(kind).validate(payload)
        key = content_key(kind, problem)
        entry = _Entry(kind=kind, problem=problem, solution=solution,
                       nbytes=_tree_nbytes(problem) + _tree_nbytes(solution))
        with self._lock:
            self._spilled.pop(key, None)
            self._mem[key] = entry
            self._mem.move_to_end(key)
            self._shrink()
        return key

    def get(self, key: str) -> _Entry | None:
        """Entry for ``key`` (memory or spill), ``None`` + a miss if absent."""
        with self._lock:
            entry = self._mem.get(key)
            if entry is not None:
                self._mem.move_to_end(key)
                self.hits += 1
                return entry
            entry = self._unspill(key)
            if entry is not None:
                self.hits += 1
                return entry
            self.misses += 1
            return None

    def stats(self) -> dict:
        with self._lock:
            total = self.hits + self.misses
            return {
                "entries": len(self._mem), "spilled": len(self._spilled),
                "nbytes": sum(e.nbytes for e in self._mem.values()),
                "hits": self.hits, "misses": self.misses,
                "hit_rate": (self.hits / total) if total else None,
            }

    def _shrink(self) -> None:
        while (len(self._mem) > self.max_entries
               or self.nbytes > self.max_bytes):
            if len(self._mem) == 1 and len(self._mem) <= self.max_entries:
                break                       # never evict the sole entry
            key, entry = self._mem.popitem(last=False)
            self._spill(key, entry)

    def _spill(self, key: str, entry: _Entry) -> None:
        if self.spill_dir is None:
            return                          # plain eviction
        from repro.checkpoint import store
        p_leaves, p_def = jax.tree.flatten(entry.problem)
        s_leaves, s_def = jax.tree.flatten(entry.solution)
        store.put(self.spill_dir, key, list(p_leaves) + list(s_leaves))
        self._spilled[key] = (entry.kind, p_def, s_def, len(p_leaves),
                              entry.nbytes)

    def _unspill(self, key: str) -> _Entry | None:
        meta = self._spilled.get(key)
        if meta is None:
            return None
        from repro.checkpoint import store
        leaves = store.get(self.spill_dir, key)
        if leaves is None:                  # spill file vanished
            del self._spilled[key]
            return None
        kind, p_def, s_def, n_p, nbytes = meta
        entry = _Entry(kind=kind,
                       problem=jax.tree.unflatten(p_def, leaves[:n_p]),
                       solution=jax.tree.unflatten(s_def, leaves[n_p:]),
                       nbytes=nbytes)
        del self._spilled[key]
        self._mem[key] = entry              # promote back to memory
        self._shrink()
        return entry


# --------------------------------------------------------------- the driver


def _lead_axis(spec, leaf, batch_ndim: int = 1) -> int:
    fn = getattr(spec, "lead_axes_fn", None)
    return fn(leaf, batch_ndim) if fn is not None else 0


def _concat_problems(stacked1: list):
    """Concatenate batch-1 problems along the leading (public) batch axis."""
    if len(stacked1) == 1:
        return stacked1[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *stacked1)


def _concat_states(spec, states1: list):
    """Concatenate batch-1 states along each leaf's batch axis."""
    if len(states1) == 1:
        return states1[0]

    def cat(*xs):
        return jnp.concatenate(xs, axis=_lead_axis(spec, xs[0]))

    return jax.tree.map(cat, *states1)


def build_warm_state(kind_obj, rt, warm_fn, problem1, payload, ws, bshape):
    """One warm instance's state: resolve the base, bound the delta, call
    the kind's ``warm_state`` hook.  Shared by ``solve_warm`` and the
    refill session's warm admissions."""
    base1, bound = None, ws.delta_bound
    if ws.base_problem is not None:
        base = kind_obj.validate(ws.base_problem)
        if bound is None:
            bound = delta_bound(payload, base)
        base1 = rt.pad_one(base, bshape)
    return warm_fn(problem1, ws.solution, base_problem1=base1,
                   delta_bound=bound)


def solve_warm(kind: str, payloads: Sequence, warm: dict | None = None, *,
               bucket: str = "max", compact: bool = False, mesh=None,
               mesh_axis: str | None = None, stats_out: list | None = None,
               **solver_kw) -> list:
    """Solve ``payloads`` with per-instance warm starts mixed into the
    ordinary cold buckets; returns per-payload results in input order.

    ``warm`` maps payload positions to ``WarmStart``s; positions absent
    from it are cold-initialized through the kind's ``ops.init`` — inside
    the SAME bucket, so a mixed batch costs one dispatch.  Drivers
    (``repro.core.solver_loop.solve`` from the built state): the jitted
    masked loop by default, ``run_compacted`` under ``compact=True``,
    per-device compacted lanes when ``mesh`` is given.  ``stats_out`` (a
    list) receives one ``BucketStats`` per dispatched bucket, exactly like
    ``repro.core.batch.solve_batch``.
    """
    from repro.core.batch import BucketStats, _bucket_shape

    k = get_kind(kind)
    for hook in ("ops", "warm_state"):
        if getattr(k, hook) is None:
            raise ValueError(
                f"solver kind {kind!r} registered no {hook!r} hook; it "
                f"cannot warm-start (serve it cold through solve_batch)")
    warm = dict(warm or {})
    for pos in warm:
        if not 0 <= pos < len(payloads):
            raise ValueError(
                f"warm position {pos} out of range for "
                f"{len(payloads)} payloads")
        if not isinstance(warm[pos], WarmStart):
            raise TypeError(
                f"warm[{pos}] must be a WarmStart, "
                f"got {type(warm[pos]).__name__}")

    rt = k.ops(**solver_kw)
    warm_fn = k.warm_state(**solver_kw)
    validated = [k.validate(p) for p in payloads]
    shapes = [rt.shape_of(p) for p in validated]
    if not validated:
        return []

    # group positions by bucket shape — warm and cold share buckets
    ndim = len(shapes[0])
    max_shape = tuple(max(s[d] for s in shapes) for d in range(ndim))
    groups: dict[tuple, list[int]] = {}
    for i, s in enumerate(shapes):
        groups.setdefault(_bucket_shape(s, bucket, max_shape), []).append(i)

    results: dict[int, Any] = {}
    for bshape, idxs in groups.items():
        problems1 = [rt.pad_one(validated[i], bshape) for i in idxs]
        n = len(idxs)
        if mesh is not None:
            # pad with inert instances so the batch divides the shard
            # count, exactly like solve_batch's mesh path
            from repro.launch.mesh import shard_count
            n_pad = -n % shard_count(mesh, mesh_axis)
            problems1 += [rt.pad_one(k.inert_problem(bshape), bshape)] * n_pad
        states1 = [build_warm_state(k, rt, warm_fn, p1, validated[i],
                                    warm[i], bshape)
                   if i in warm else rt.init(p1)
                   for i, p1 in zip(idxs, problems1)]
        states1 += [rt.init(p1) for p1 in problems1[n:]]
        res = solve(rt, _concat_problems(problems1), (len(problems1),),
                    compact=compact, mesh=mesh, mesh_axis=mesh_axis,
                    state=_concat_states(rt.spec, states1))
        for b, i in enumerate(idxs):
            results[i] = rt.crop(res, b, shapes[i], validated[i])
        if stats_out is not None:
            r = np.asarray(res.rounds)[:n]
            stats_out.append(BucketStats(
                kind=kind, shape=bshape, n_real=n, n_pad=0,
                compact=bool(compact or mesh is not None),
                rounds_min=int(r.min()), rounds_max=int(r.max()),
                rounds_mean=float(r.mean()),
                n_converged=int(np.asarray(res.converged)[:n].sum())))
    return [results[i] for i in range(len(payloads))]
