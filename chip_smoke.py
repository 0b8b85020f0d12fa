#!/usr/bin/env python3
"""Smoke run of the solver service on a TPU, checked against host oracles.

Usage, from the root of a checkout on a TPU host::

    python3 chip_smoke.py [--seed N]              # one chip
    python3 chip_smoke.py --chips 4 [--seed N]    # the mesh paths, 4 chips

One chip: the three solver kinds through ``solve_batch`` at the paper's
sizes under every backend (maxflow: 8 grids of 512² under xla, pallas and
balanced; assignment: auction on 4 dense n=1024 matrices and 64 of the
paper's n=30 under xla and pallas; matching: 4 random 1024×1024 graphs
under xla and pallas), then a mixed stream of 48 requests of all three
kinds through ``AsyncSolverEngine.submit``, once in closed batches and
once with continuous batching (``refill=True``).

``--chips 4`` runs only the paths that exist across chips, against the
unsharded one-device solve: ``solve_batch(mesh=make_solver_mesh())``
for each kind, masked and ``compact=True``, and the same served stream
through ``AsyncSolverEngine(mesh=...)`` with its lanes on disjoint
two-chip sub-meshes.

Every phase prints one line: compile seconds (first call minus a repeat
call), solve seconds (the repeat call, timed after ``block_until_ready``)
and its checks. These are smoke timings, not benchmark numbers. Data
comes from ``--seed`` through the repo's own generators. The last line of
standard output is ``{"ok": true, "device": {...}}``, printed only when
every phase passed; any failed check raises and exits non-zero. Without a
TPU the script exits non-zero at once: it never falls back to the CPU.

JAX's persistent compilation cache lives in ``JAX_COMPILATION_CACHE_DIR``
when that is set, else in ``.jax_cache`` next to this file.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

FUTURE_TIMEOUT_S = 600.0


@dataclasses.dataclass(frozen=True)
class Sizes:
    grid: int = 512          # configs.paper_flow.GRID_BENCH.grid
    grids: int = 8
    assign_n: int = 1024     # dense assignment beyond the paper's n <= 30
    assign_batch: int = 4
    paper_n: int = 30        # configs.paper_flow.ASSIGN_BENCH.n
    paper_batch: int = 64
    max_cost: int = 100      # configs.paper_flow.ASSIGN_BENCH.max_cost
    match_n: int = 1024
    match_batch: int = 4


class SmokeFailure(AssertionError):
    pass


def check(ok, what: str) -> str:
    if not ok:
        raise SmokeFailure(what)
    return what


def report(phase: str, compile_s, solve_s, checks: list[str]) -> None:
    c = "n/a" if compile_s is None else f"{compile_s:.3f}"
    print(f"[{phase}] compile_s={c} solve_s={solve_s:.3f} "
          f"checks: {'; '.join(checks)}", flush=True)


def timed_twice(fn):
    """``fn()`` twice: (first result, repeat result, compile_s, solve_s)."""
    import jax
    t0 = time.perf_counter()
    first = jax.block_until_ready(fn())
    t1 = time.perf_counter()
    again = jax.block_until_ready(fn())
    t2 = time.perf_counter()
    return first, again, (t1 - t0) - (t2 - t1), t2 - t1


def lowered_has_tpu_kernel(entry, stacked, **kw) -> bool:
    """Whether ``entry(stacked, **kw)`` lowers to a Mosaic kernel call."""
    import jax
    text = jax.jit(functools.partial(entry, **kw)).lower(stacked).as_text()
    return "tpu_custom_call" in text


# ------------------------------------------------------------------ data

def make_data(seed: int, s: Sizes) -> dict:
    from repro.core.matching.ref import random_bipartite
    from repro.core.maxflow.grid import GridProblem
    from repro.core.maxflow.ref import random_grid_problem
    rng = np.random.default_rng(seed)
    grids = [GridProblem(*random_grid_problem(
        rng, s.grid, s.grid, max_cap=20, terminal_density=0.3))
        for _ in range(s.grids)]
    big = [rng.integers(0, s.max_cost + 1, (s.assign_n, s.assign_n))
           for _ in range(s.assign_batch)]
    paper = [rng.integers(0, s.max_cost + 1, (s.paper_n, s.paper_n))
             for _ in range(s.paper_batch)]
    graphs = [random_bipartite(rng, s.match_n, s.match_n)
              for _ in range(s.match_batch)]
    return dict(grids=grids, big=big, paper=paper, graphs=graphs)


def oracles(data: dict) -> dict:
    """Host answers: scipy max-flow, Hungarian weight, Hopcroft-Karp."""
    from repro.core.assignment.ref import optimal_weight
    from repro.core.matching.ref import hopcroft_karp
    from repro.core.maxflow.ref import maxflow_grid_ref
    t0 = time.perf_counter()
    out = dict(
        grids=[maxflow_grid_ref(*p) for p in data["grids"]],
        big=[optimal_weight(w) for w in data["big"]],
        paper=[optimal_weight(w) for w in data["paper"]],
        graphs=[hopcroft_karp(a)[2] for a in data["graphs"]])
    print(f"[oracles] host_s={time.perf_counter() - t0:.3f}", flush=True)
    return out


# ------------------------------------------------------ per-result checks

def maxflow_ok(res, ref: int) -> bool:
    return bool(res.converged) and float(res.flow) == float(ref)


def assignment_ok(res, w, ref: int) -> bool:
    col = np.asarray(res.col_of_row)
    n = w.shape[0]
    perm = np.array_equal(np.sort(col), np.arange(n))
    return (bool(res.converged) and perm and int(res.weight) == ref
            and int(np.asarray(w)[np.arange(n), col].sum()) == ref)


def matching_ok(res, adj, ref: int) -> bool:
    mr = np.asarray(res.match_row)
    rows = np.nonzero(mr >= 0)[0]
    valid = (bool(np.all(adj[rows, mr[rows]]))
             and len(set(mr[rows].tolist())) == rows.size)
    return (bool(res.converged) and valid and rows.size == ref
            and int(res.cardinality) == ref)


def count(flags) -> str:
    flags = list(flags)
    return f"{sum(flags)}/{len(flags)}"


def same_tree(a, b) -> bool:
    import jax
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


# ------------------------------------------------------------- one chip

def phase_maxflow(data, ref, s: Sizes) -> None:
    from repro.core import maxflow_grid_batch, prepare_buckets, solve_batch
    probs = data["grids"]
    [prep] = prepare_buckets("maxflow", probs)
    base = None
    for backend in ("xla", "pallas", "balanced"):
        res, again, comp, solve = timed_twice(
            lambda: solve_batch("maxflow", probs, backend=backend))
        ok = [maxflow_ok(r, f) for r, f in zip(res, ref["grids"])]
        checks = [
            check(same_tree(res, again), "repeat==first"),
            check(all(ok), f"converged+flow==scipy {count(ok)}")]
        if base is None:
            base = res
        else:
            checks.append(check(
                all(float(r.flow) == float(b.flow)
                    and np.array_equal(np.asarray(r.cut), np.asarray(b.cut))
                    for r, b in zip(res, base)),
                "flow+cut==xla bit-for-bit"))
            checks.append(check(
                lowered_has_tpu_kernel(maxflow_grid_batch, prep.stacked,
                                       backend=backend),
                "tpu_custom_call"))
        report(f"maxflow/{backend} {len(probs)}x{s.grid}x{s.grid}",
               comp, solve, checks)


def phase_assignment(data, ref, s: Sizes) -> None:
    from repro.core import prepare_buckets, solve_assignment, solve_batch
    for key, n in (("big", s.assign_n), ("paper", s.paper_n)):
        costs = data[key]
        [prep] = prepare_buckets("assignment", costs)
        for backend in ("xla", "pallas"):
            res, again, comp, solve = timed_twice(
                lambda: solve_batch("assignment", costs, method="auction",
                                    backend=backend))
            ok = [assignment_ok(r, w, f)
                  for r, w, f in zip(res, costs, ref[key])]
            checks = [check(same_tree(res, again), "repeat==first"),
                      check(all(ok), f"converged+weight==hungarian "
                                     f"{count(ok)}")]
            if backend == "pallas":
                checks.append(check(
                    lowered_has_tpu_kernel(solve_assignment, prep.stacked,
                                           method="auction",
                                           backend=backend),
                    "tpu_custom_call"))
            report(f"assignment/auction/{backend} {len(costs)}x{n}x{n}",
                   comp, solve, checks)


def phase_matching(data, ref, s: Sizes) -> None:
    from repro.core import match_bipartite_batch, prepare_buckets, solve_batch
    graphs = data["graphs"]
    [prep] = prepare_buckets("matching", graphs)
    for backend in ("xla", "pallas"):
        res, again, comp, solve = timed_twice(
            lambda: solve_batch("matching", graphs, backend=backend))
        ok = [matching_ok(r, a, f)
              for r, a, f in zip(res, graphs, ref["graphs"])]
        checks = [check(same_tree(res, again), "repeat==first"),
                  check(all(ok), f"converged+card==hopcroft_karp "
                                 f"{count(ok)}")]
        if backend == "pallas":
            checks.append(check(
                lowered_has_tpu_kernel(match_bipartite_batch, prep.stacked,
                                       backend=backend),
                "tpu_custom_call"))
        report(f"matching/{backend} {len(graphs)}x{s.match_n}x{s.match_n}",
               comp, solve, checks)


def served_stream(data, ref, s: Sizes) -> list[tuple]:
    """48 (kind, payload, checker) requests, kinds interleaved.

    Per kind, equal-size requests stay together so every flush fills one
    bucket: 8 grids, then 32 paper-size and 4 n=1024 assignments, then 4
    matchings.
    """
    from repro.core.maxflow.grid import GridProblem
    per_kind = [
        [("maxflow", GridProblem(*p),
          functools.partial(maxflow_ok, ref=f))
         for p, f in zip(data["grids"], ref["grids"])],
        [("assignment", w, functools.partial(assignment_ok, w=w, ref=f))
         for w, f in list(zip(data["paper"], ref["paper"]))[:32]
         + list(zip(data["big"], ref["big"]))],
        [("matching", a, functools.partial(matching_ok, adj=a, ref=f))
         for a, f in zip(data["graphs"], ref["graphs"])],
    ]
    stream = []
    for i in range(max(map(len, per_kind))):
        stream += [q[i] for q in per_kind if i < len(q)]
    return stream


SERVED_KW = {k: {"backend": "pallas"}
             for k in ("maxflow", "assignment", "matching")}


def phase_served(data, ref, s: Sizes, *, mesh=None, tag: str = "") -> None:
    from repro.serve.scheduler import AsyncSolverEngine
    stream = served_stream(data, ref, s)
    for refill in (False, True):
        t0 = time.perf_counter()
        with AsyncSolverEngine(max_batch=8, max_delay_ms=2000.0,
                               bucket="pow2", dispatch="masked",
                               refill=refill, mesh=mesh,
                               solver_kw=SERVED_KW) as eng:
            futs = [eng.submit(kind, payload) for kind, payload, _ in stream]
            results = [f.result(timeout=FUTURE_TIMEOUT_S) for f in futs]
        wall = time.perf_counter() - t0
        snap = eng.metrics.snapshot()
        ok = [chk(r) for r, (_, _, chk) in zip(results, stream)]
        isolated = {k: eng.metrics.dispatch_count(k, "isolated")
                    for k in ("maxflow", "assignment", "matching")}
        checks = [
            check(all(ok), f"results==oracles {count(ok)}"),
            check(snap["tickets"].get("completed") == len(stream)
                  and not snap["tickets"].get("failed"),
                  f"completed {snap['tickets'].get('completed')}"),
            check(sum(isolated.values()) == 0,
                  f"isolated dispatches {sum(isolated.values())}"),
            f"dispatches {snap['dispatches']}",
        ]
        if refill:
            sessions = snap["refill"]["sessions"]
            checks.append(check(sum(sessions.values()) > 0,
                                f"refill sessions {sessions}"))
            checks.append(f"refill admitted {snap['refill']['admitted']}")
        # compiles are included: the stream reuses the batch phases'
        # programs where shapes match, and compiles the rest on the fly
        report(f"served{tag}/{'refill' if refill else 'closed'} "
               f"{len(stream)} requests", None, wall, checks)


def run_one_chip(seed: int, s: Sizes = Sizes()) -> None:
    data = make_data(seed, s)
    ref = oracles(data)
    phase_maxflow(data, ref, s)
    phase_assignment(data, ref, s)
    phase_matching(data, ref, s)
    phase_served(data, ref, s)


# ------------------------------------------------------------ four chips

def device_ids(tree) -> set:
    import jax
    return {d.id for leaf in jax.tree.leaves(tree)
            for d in leaf.sharding.device_set}


def run_mesh(seed: int, n_chips: int, s: Sizes = Sizes()) -> None:
    """Sharded and compacted-lane solves vs the unsharded one-device solve."""
    import jax
    from repro.core import (match_bipartite_batch, maxflow_grid_batch,
                            prepare_buckets, solve_assignment, solve_batch)
    from repro.launch.mesh import make_solver_mesh, scheduler_lanes
    mesh = make_solver_mesh()
    all_ids = {d.id for d in mesh.devices.reshape(-1)}
    check(len(all_ids) == n_chips, f"mesh spans {n_chips} devices")
    data = make_data(seed, s)
    ref = oracles(data)
    cases = [
        ("maxflow", data["grids"], maxflow_grid_batch,
         dict(backend="pallas")),
        ("assignment", data["big"], solve_assignment,
         dict(method="auction", backend="pallas")),
        ("matching", data["graphs"], match_bipartite_batch,
         dict(backend="pallas")),
    ]
    for kind, payloads, entry, kw in cases:
        base = jax.block_until_ready(solve_batch(kind, payloads, **kw))
        for compact in (False, True):
            checks = []
            res, again, comp, solve = timed_twice(
                lambda: solve_batch(kind, payloads, mesh=mesh,
                                    compact=compact, **kw))
            checks.append(check(
                same_tree(res, base) and same_tree(again, base),
                f"{'compacted' if compact else 'sharded'}==unsharded "
                f"bit-for-bit"))
            [prep] = prepare_buckets(kind, payloads, mesh=mesh)
            raw = jax.block_until_ready(
                entry(prep.stacked, mesh=mesh, compact=compact, **kw))
            ids = device_ids(raw)
            if compact:
                # compacted lanes reassemble on the first lane's device
                checks.append(check(
                    ids == {mesh.devices.reshape(-1)[0].id},
                    f"compacted result on device {sorted(ids)}"))
            else:
                checks.append(check(ids == all_ids,
                                    f"sharded result on devices "
                                    f"{sorted(ids)}"))
            report(f"{kind}/{kw['backend']}/mesh{n_chips}"
                   f"{'/compact' if compact else ''} {len(payloads)} inst",
                   comp, solve, checks)
    # a scheduler lane's sub-mesh: compacted lanes reassemble on ITS chips
    sub = scheduler_lanes(mesh, n_lanes=2)[1]
    [prep] = prepare_buckets("maxflow", data["grids"], mesh=sub)
    raw, _, comp, solve = timed_twice(lambda: maxflow_grid_batch(
        prep.stacked, mesh=sub, compact=True, backend="pallas"))
    ids = device_ids(raw)
    home = sub.devices.reshape(-1)[0].id
    flows = [float(f) for f in raw.flow]
    report(f"maxflow/pallas/lane-submesh/compact {len(flows)} inst",
           comp, solve,
           [check(ids == {home}, f"reassembled on device {sorted(ids)}"),
            check(flows == [float(f) for f in ref["grids"]],
                  "flow==scipy")])
    phase_served(data, ref, s, mesh=mesh, tag=f"/mesh{n_chips}")


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh-sharded paths on 4 chips")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX has "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.kernels import interpret_mode
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache(ROOT / ".jax_cache")
    check(not interpret_mode(), "kernels compile (no interpret mode)")
    print(f"[setup] device={dev.device_kind} count={len(devices)} "
          f"jax={jax.__version__} compile_cache={cache}", flush=True)

    t0 = time.perf_counter()
    if args.chips == 1:
        run_one_chip(args.seed)
    else:
        run_mesh(args.seed, args.chips)
    print(f"[total] wall_s={time.perf_counter() - t0:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
