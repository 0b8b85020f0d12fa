"""Mesh construction + sharding specs for the solver stack and the models.

Every factory is a function (not a constant): importing this module must
never touch jax device state.

Two mesh families live here:

* model meshes (``make_production_mesh`` / ``make_host_mesh``) — the
  ``("data", "model")`` meshes the transformer stack shards over, and
* solver meshes (``make_solver_mesh``) — a 1-D ``("batch",)`` mesh for the
  batched flow/matching solvers, whose batch axis is embarrassingly
  data-parallel (per-instance liveness masks make every instance's
  trajectory independent of its batch-mates, so shards never communicate).

``shard_batched`` is the one sharding primitive the solver stack uses: it
wraps a batch-leading function in ``shard_map`` with the leading axis
partitioned across the mesh and everything else replicated. Because the
wrapped solvers contain no collectives, each device runs its local shard's
while-loops to local convergence — a fully-converged shard simply finishes
its dispatch early. Results bit-match the unsharded batched solve
(tests/test_shard.py).

``compact_lanes`` is the compaction analogue: it splits the batch into
per-shard host-driven lanes (one per device) for the solvers' ``compact=``
paths, keeping early-exit compaction within each shard (tests/test_compact.py).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import numpy as np
from jax import shard_map
from jax.sharding import AxisType, PartitionSpec


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi-pod prepends a 2-pod axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model_parallel: int = 1):
    """Whatever this process actually has (smoke tests, examples)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"),
                         axis_types=(AxisType.Auto, AxisType.Auto))


def make_solver_mesh(n_devices: int | None = None, *, axis: str = "batch"):
    """1-D device mesh for batch-axis sharding of the batched solvers.

    Args:
      n_devices: how many local devices to use (default: all). Emulate a
        multi-device host on CPU with
        ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
      axis: mesh axis name; the solvers' default sharding axis is "batch".

    Returns a ``jax.sharding.Mesh`` accepted by the ``mesh=`` knob of
    every registered solver kind's batched entry point
    (``maxflow_grid_batch`` / ``solve_assignment`` /
    ``match_bipartite_batch`` / ...), of the generic ragged front end
    ``repro.core.batch.solve_batch``, and of the serving engines
    (``repro.serve``).
    """
    devs = jax.devices()
    if n_devices is not None:
        if not 1 <= n_devices <= len(devs):
            raise ValueError(
                f"n_devices={n_devices} outside [1, {len(devs)}] available")
        devs = devs[:n_devices]
    return jax.sharding.Mesh(np.array(devs), (axis,))


def solver_batch_axis(mesh, mesh_axis: str | None = None) -> str:
    """The mesh axis the batch dimension shards over (default: first axis)."""
    axis = mesh_axis if mesh_axis is not None else mesh.axis_names[0]
    if axis not in mesh.axis_names:
        raise ValueError(f"axis {axis!r} not in mesh axes {mesh.axis_names}")
    return axis


def shard_count(mesh, mesh_axis: str | None = None) -> int:
    """Number of shards the batch axis splits into on ``mesh``."""
    return int(mesh.shape[solver_batch_axis(mesh, mesh_axis)])


def batch_spec(mesh, mesh_axis: str | None = None) -> PartitionSpec:
    """PartitionSpec sharding a leading batch axis; trailing axes replicate.

    Used as a pytree-prefix spec: one ``PartitionSpec("batch")`` covers every
    leaf of the solvers' problem/result pytrees, because every public leaf
    leads with the batch axis.
    """
    return PartitionSpec(solver_batch_axis(mesh, mesh_axis))


def compact_lanes(mesh, mesh_axis: str | None, batch_size: int):
    """Per-shard ``(lo, hi, device)`` lanes for compacted solving on ``mesh``.

    Early-exit compaction (``repro.core.solver_loop.run_compacted``) under a
    mesh stays WITHIN each shard: every shard becomes an independent
    host-driven compaction lane pinned to its device, instances never
    migrate between shards, and no collectives are introduced — so the
    shard-independence contract (and the bit-match with the unsharded and
    masked paths) is preserved. Requires one device per shard, i.e. the 1-D
    solver meshes of ``make_solver_mesh``.
    """
    n = shard_count(mesh, mesh_axis)
    if batch_size % n:
        raise ValueError(
            f"batch size {batch_size} not divisible by shard count "
            f"{n}; pad the batch (repro.core.batch does this "
            f"automatically)")
    if int(mesh.devices.size) != n:
        raise ValueError(
            f"compact=True needs one device per shard (a 1-D solver mesh); "
            f"this mesh has {int(mesh.devices.size)} devices for {n} shards")
    per = batch_size // n
    devs = list(mesh.devices.reshape(-1))
    return [(i * per, (i + 1) * per, devs[i]) for i in range(n)]


def scheduler_lanes(mesh, mesh_axis: str | None = None, n_lanes: int = 2):
    """Per-lane meshes for the async scheduler's double-buffered dispatch.

    The serving scheduler (``repro.serve.scheduler.AsyncSolverEngine``)
    keeps ``n_lanes`` dispatch lanes so batch *k+1*'s host padding overlaps
    batch *k*'s device solve. This helper decides what each lane dispatches
    ON:

    * ``mesh is None`` — every lane gets ``None`` (default device; overlap
      is host-vs-device pipelining only).
    * mesh with >= ``n_lanes`` devices — the mesh's devices split into
      ``n_lanes`` contiguous DISJOINT sub-meshes (remainder devices go to
      the leading lanes), so two in-flight batches run on different
      hardware concurrently, not just back-to-back in one device queue.
    * fewer devices than lanes — every lane shares the full mesh.

    Results are unaffected either way: sharded solves bit-match unsharded
    ones (tests/test_shard.py), so WHICH sub-mesh a batch lands on never
    changes its values. Requires a 1-D solver mesh (``make_solver_mesh``).
    """
    if n_lanes < 1:
        raise ValueError(f"n_lanes must be >= 1, got {n_lanes}")
    if mesh is None:
        return [None] * n_lanes
    axis = solver_batch_axis(mesh, mesh_axis)
    devs = list(mesh.devices.reshape(-1))
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"scheduler_lanes needs a 1-D solver mesh, got axes "
            f"{mesh.axis_names}")
    if len(devs) < n_lanes:
        return [mesh] * n_lanes
    per, rem = divmod(len(devs), n_lanes)
    lanes, lo = [], 0
    for i in range(n_lanes):
        hi = lo + per + (1 if i < rem else 0)
        lanes.append(jax.sharding.Mesh(np.array(devs[lo:hi]), (axis,)))
        lo = hi
    return lanes


def shard_batched(fn: Callable, mesh, mesh_axis: str | None = None):
    """Wrap a batch-leading ``fn`` so the batch axis splits across ``mesh``.

    ``fn`` must take array/pytree arguments whose every leaf has the batch
    dimension leading, and return a pytree with the same property. The
    returned callable is ``jit(shard_map(fn))`` with the batch axis
    partitioned and no replication checking (the solvers are collective-free,
    every output is sharded).

    The caller is responsible for ``B % shard_count(mesh) == 0``; the core
    entry points raise a ``ValueError`` otherwise and the pad-and-bucket
    front end pads with inert instances instead.
    """
    spec = batch_spec(mesh, mesh_axis)
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=spec, out_specs=spec,
                             check_vma=False))


_cached_shard_batched = functools.lru_cache(maxsize=None)(shard_batched)


def dispatch_sharded(impl: Callable, args: tuple, batch_size: int, mesh,
                     mesh_axis: str | None):
    """Run batched ``impl(*args)`` with the batch axis sharded.

    The one mesh-dispatch funnel (``repro.core.solver_loop.solve``):
    validates ``batch_size`` divides the shard count, memoizes the
    jit(shard_map(...)) callable per (impl, mesh, mesh_axis), and calls
    it. ``impl`` must be hashable and stable across calls (a kind's
    cached ``KindOps.solve``).
    """
    n_shards = shard_count(mesh, mesh_axis)
    if batch_size % n_shards:
        raise ValueError(
            f"batch size {batch_size} not divisible by shard count "
            f"{n_shards}; pad the batch (repro.core.batch does this "
            f"automatically)")
    return _cached_shard_batched(impl, mesh, mesh_axis)(*args)
