"""Solver-kind registry: the one seam every layer above the kernels shares.

The stack above the solvers — the ragged pad-and-bucket front end
(``repro.core.batch``), warm starts (``repro.core.warm``), continuous
batching (``repro.core.refill``), the serving engine
(``repro.serve.engine``) and the async scheduler
(``repro.serve.scheduler``) — dispatches through this REGISTRY: a solver
kind registers once, under a string name, the capabilities the upper
layers need, and every layer looks it up with ``get_kind``.  Adding a kind
is one solver module (a ``LoopSpec``, its instance operations and
kernels) plus one ``register_kind`` call — ``repro.core.matching`` (GPU
bipartite maximum-cardinality matching, Deveci et al., arXiv:1303.1379)
is the third kind; see docs/solvers.md for the walkthrough.

A ``SolverKind`` bundles:

* ``validate(payload) -> payload`` — canonicalize + reject a malformed
  request (raises ``ValueError``) BEFORE any ticket or future exists; the
  submit-time contract of both serving engines.
* ``inert_problem(shape) -> payload`` — an instance that converges
  immediately and cannot perturb batch-mates; the pad-and-bucket front end
  appends these so every bucket splits evenly across a device mesh.
* ``prepare_buckets(payloads, *, bucket=, mesh=, mesh_axis=)`` — the HOST
  stage: pad, bucket, and stack a ragged queue into ``PreparedBucket``s.
* ``solve_prepared(prep, *, compact=, mesh=, mesh_axis=, **kw)`` — the
  DEVICE stage: one batched dispatch of a prepared bucket, returning
  ``({payload_position: result}, BucketStats)``.
* ``ops(**static_kw) -> KindOps`` — OPTIONAL (default ``None``): the
  kind's cached instance operations (``repro.core.solver_loop.KindOps``):
  its ``LoopSpec``, jitted init / finalize / fused masked solve, pad-one,
  shape-of and the jitted per-instance crop.  The kind's batched entry
  point hands them to ``repro.core.solver_loop.solve``, the one driver
  ladder (masked, host-stepped, compacted, sharded); refill sessions and
  warm starts build and finish their states with the same pieces, so
  every path bit-matches the closed batch.  Kinds without it serve
  through the closed-batch path only.

Two further OPTIONAL hooks complete the warm-start seam
(``repro.core.warm`` drives them; all three builtin kinds register both):

* ``warm_state(**static_kw) -> (problem1, solution, base_problem1=,
  delta_bound=) -> state1`` — rebuild a VALID loop state for the (possibly
  delta-mutated) ``problem1`` from a previously cached ``solution``:
  clamp the prior preflow to the new capacities and repair deficits while
  keeping heights valid lower bounds (maxflow); re-enter the ε-ladder at
  a delta-bounded rung with the prior prices (assignment); keep the still-
  valid matched pairs and re-run augmenting rounds (matching).  The warm
  state must drive the UNCHANGED loop to the same optimum a cold solve of
  the mutated problem reaches; cold instances of the same batch start
  from ``ops.init``.
* ``solution_of(result) -> solution`` — extract the cacheable artifact
  (the thing ``warm_state`` consumes) from one cropped per-instance
  result; ``repro.core.warm.SolutionCache`` stores and spills these.

This module imports neither jax nor the solver packages at import time —
the registry stays importable from anywhere (``repro.serve.metrics``
included) without touching device state.  The built-in kinds register
themselves when their home modules import; ``get_kind`` /
``registered_kinds`` lazily import those modules so lookups work no matter
which module the caller imported first.
"""
from __future__ import annotations

import importlib
from typing import Any, Callable, NamedTuple

__all__ = ["SolverKind", "register_kind", "get_kind", "registered_kinds"]


class SolverKind(NamedTuple):
    """One solver kind's registration — see the module docstring."""

    name: str
    validate: Callable[[Any], Any]
    inert_problem: Callable[..., Any]
    prepare_buckets: Callable[..., list]
    solve_prepared: Callable[..., tuple]
    # optional: the cached KindOps factory over the kind's static solver
    # knobs (repro.core.solver_loop); None = closed-batch only
    ops: Callable[..., Any] | None = None
    # optional warm-start seam (repro.core.warm); None = cold-only kind.
    # warm_state is a factory over the static knobs returning a
    # per-instance (batch-1) state builder; solution_of maps one cropped
    # result to its cacheable artifact.
    warm_state: Callable[..., Any] | None = None
    solution_of: Callable[[Any], Any] | None = None


_REGISTRY: dict[str, SolverKind] = {}

# Modules that register the built-in kinds as an import side effect.  Lazy
# (imported on first lookup, not at this module's import) so the registry
# itself never drags jax in, and so circular imports cannot form: these
# modules import ``repro.core.kinds`` at their top, we import them only
# from inside a function call.
_BUILTIN_MODULES = ("repro.core.batch", "repro.core.matching")


def _ensure_builtins() -> None:
    for mod in _BUILTIN_MODULES:
        importlib.import_module(mod)


def register_kind(kind: SolverKind) -> SolverKind:
    """Register ``kind`` under ``kind.name``; returns it for convenience.

    Duplicate names are an error (a silent overwrite would let two modules
    fight over a name and make dispatch order-of-import dependent).  There
    is deliberately no unregister: kinds are process-lifetime registrations,
    like jax's pytree registrations.
    """
    if not kind.name or not isinstance(kind.name, str):
        raise ValueError(f"kind name must be a non-empty string, "
                         f"got {kind.name!r}")
    if kind.name in _REGISTRY:
        raise ValueError(
            f"solver kind {kind.name!r} is already registered; kind names "
            f"must be unique (registered: {sorted(_REGISTRY)})")
    _REGISTRY[kind.name] = kind
    return kind


def get_kind(name: str) -> SolverKind:
    """Look up a registered kind; unknown names raise naming the known ones."""
    _ensure_builtins()
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown solver kind {name!r}; registered kinds: "
            f"{', '.join(registered_kinds())}") from None


def registered_kinds(*, ensure: bool = True) -> tuple[str, ...]:
    """Names of every registered kind, in registration order.

    Built-in kinds (``maxflow``, ``assignment``, ``matching``) are ensured
    first, so the result is stable regardless of which module the caller
    imported.  Pass ``ensure=False`` to only PEEK at what has registered so
    far without importing the (jax-heavy) builtin solver modules — the
    jax-free metrics layer uses this.
    """
    if ensure:
        _ensure_builtins()
    return tuple(_REGISTRY)
