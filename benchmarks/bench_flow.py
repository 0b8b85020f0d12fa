"""Benchmarks mirroring the paper's evaluation (§6 + the CUDA tables)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


# --------------------------------------------------------------- registry
#
# ``benchmarks.run`` enumerates this table instead of hardcoding choices.
# ``kind=`` ties a bench to a solver kind from ``repro.core.kinds``; the
# harness asserts every registered kind has a tied bench, so adding a
# solver kind without a benchmark fails loudly instead of silently
# shipping unmeasured.
BENCHES: dict = {}
KIND_BENCHES: dict = {}  # solver kind name -> bench name


def bench(name, *, kind=None):
    def deco(fn):
        if name in BENCHES:
            raise ValueError(f"duplicate bench name {name!r}")
        BENCHES[name] = fn
        if kind is not None:
            KIND_BENCHES[kind] = name
        return fn
    return deco


def _time(fn, *args, reps=3, **kw):
    fn(*args, **kw)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps * 1e6  # us


@bench("maxflow", kind="maxflow")
def bench_maxflow(rows, repeats=2):
    """Paper §4: push-relabel on grid graphs (vision-scale sizes)."""
    from repro.core.maxflow.grid import GridProblem, maxflow_grid
    from repro.core.maxflow.ref import random_grid_problem
    rng = np.random.default_rng(0)
    for hw in (32, 64, 128, 256):
        cap, cs, ct = random_grid_problem(rng, hw, hw, max_cap=20,
                                          terminal_density=0.3)
        prob = GridProblem(jnp.asarray(cap), jnp.asarray(cs),
                           jnp.asarray(ct))
        res = maxflow_grid(prob)
        us = _time(maxflow_grid, prob, reps=repeats)
        rows.append((f"maxflow_grid_{hw}x{hw}", us, int(res.rounds),
                     f"flow={float(res.flow):.0f};"
                     f"heuristics={int(res.heuristics)};"
                     f"Mnode_rounds_per_s="
                     f"{hw*hw*int(res.rounds)/us:.1f}"))


@bench("adversarial")
def bench_adversarial(rows, repeats=2, sizes=None):
    """Workload-balanced backend vs the paper-faithful round on adversarial
    instance families (benchmarks/RESULTS_adversarial.md).

    Three generators from ``repro.core.maxflow.ref`` stress what
    ``backend="balanced"`` changes: ``long_path`` (stranded excess must
    travel home — the bidirectional relabel's win), ``checkerboard``
    (height-plateau oscillation — the stall trigger's win), and
    ``random_wide`` (ragged wide frontier — the active-tile schedule's
    win). Every solve is oracle-checked against scipy before timing, and
    the headline metric is the ROUNDS ratio (machine-independent; the CPU
    runner times the pallas path in interpret mode, so wall-clock favours
    xla here regardless of algorithmic merit — see RESULTS_adversarial.md).

    ``sizes`` defaults to (64, 256); the CI smoke step narrows it via
    ``BENCH_ADVERSARIAL_SIZES`` (comma-separated) to stay inside its time
    budget.
    """
    import os

    from repro.core.maxflow.grid import GridProblem, maxflow_grid
    from repro.core.maxflow.ref import (ADVERSARIAL_GENERATORS,
                                        maxflow_grid_ref)
    if sizes is None:
        env = os.environ.get("BENCH_ADVERSARIAL_SIZES", "")
        sizes = tuple(int(s) for s in env.split(",") if s) or (64, 256)
    rng = np.random.default_rng(0)
    for gname, gen in ADVERSARIAL_GENERATORS.items():
        for hw in sizes:
            cap, cs, ct = gen(rng, hw, hw)
            want = maxflow_grid_ref(cap, cs, ct)
            prob = GridProblem(jnp.asarray(cap), jnp.asarray(cs),
                               jnp.asarray(ct))
            meas = {}
            for be in ("xla", "balanced"):
                res = maxflow_grid(prob, backend=be, max_rounds=500_000)
                assert bool(res.converged), (gname, hw, be)
                assert float(res.flow) == float(want), (gname, hw, be)
                us = _time(maxflow_grid, prob, backend=be,
                           max_rounds=500_000, reps=repeats)
                meas[be] = (us, int(res.rounds))
                rows.append((f"adversarial_{gname}_{hw}x{hw}_{be}", us,
                             int(res.rounds),
                             f"flow={float(res.flow):.0f};"
                             f"heuristics={int(res.heuristics)}"))
            (us_x, r_x), (us_b, r_b) = meas["xla"], meas["balanced"]
            rows.append((f"adversarial_{gname}_{hw}x{hw}_gain", us_x - us_b,
                         None,
                         f"rounds_ratio={r_x / max(r_b, 1):.2f}x;"
                         f"speedup_vs_xla={us_x / us_b:.2f}x"))


@bench("batched")
def bench_batched(rows, repeats=2):
    """Batched multi-instance engine vs vmap-of-single (instances/sec).

    ``jax.vmap(maxflow_grid)`` is a strong baseline: vmap's while_loop
    batching rule also freezes converged lanes via selects, so its results
    (including per-instance round counters) are bit-identical to the
    explicit engine. What the comparison measures is the overhead of the
    FIRST-CLASS batch axis (hand-rolled liveness masks + selects, explicit
    (B, ...) layouts) relative to the vmap program transform; what the
    explicit engine buys instead of speed is the ragged pad-and-bucket
    front end, the public batched layout, and a place to hang compaction /
    batch-axis sharding (ROADMAP). B=1 measures the mask overhead alone.
    """
    from repro.core.batch import stack_grid_problems
    from repro.core.maxflow.grid import GridProblem, maxflow_grid_batch
    from repro.core.maxflow import grid as grid_mod
    from repro.core.maxflow.ref import random_grid_problem
    import jax
    rng = np.random.default_rng(0)
    hw = 64
    raw = [GridProblem(*map(jnp.asarray, random_grid_problem(
        rng, hw, hw, max_cap=20, terminal_density=0.3))) for _ in range(64)]

    def vmap_flow(prob):  # baseline: vmap the single-instance solver.
        # Returns the same outputs as the batched engine (flow AND cut) so
        # XLA cannot dead-code-eliminate the final min-cut BFS.
        def one(c, s, t):
            r = grid_mod.maxflow_grid(GridProblem(c, s, t))
            return r.flow, r.cut, r.converged
        return jax.vmap(one)(prob.cap_nbr, prob.cap_src, prob.cap_sink)

    vmap_flow = jax.jit(vmap_flow)
    for B in (1, 8, 64):
        prob = stack_grid_problems(raw[:B])
        res = maxflow_grid_batch(prob)
        us = _time(maxflow_grid_batch, prob, reps=repeats)
        us_v = _time(vmap_flow, prob, reps=repeats)
        rows.append((f"maxflow_batch_B{B}_{hw}x{hw}", us,
                     f"inst_per_s={B / us * 1e6:.1f};"
                     f"vmap_inst_per_s={B / us_v * 1e6:.1f};"
                     f"speedup_vs_vmap={us_v / us:.2f}x;"
                     f"mean_flow={float(jnp.mean(res.flow)):.0f}"))

    from repro.core.assignment.cost_scaling import solve_assignment
    n = 64
    ws = jnp.asarray(np.stack([
        np.random.default_rng(i).integers(0, 101, (n, n))
        for i in range(64)]), jnp.int32)

    def vmap_assign(w):  # full results, comparable outputs (no DCE skew)
        return jax.vmap(solve_assignment)(w)

    vmap_assign = jax.jit(vmap_assign)
    for B in (1, 8, 64):
        w = ws[:B]
        res = solve_assignment(w)
        us = _time(solve_assignment, w, reps=repeats)
        us_v = _time(vmap_assign, w, reps=repeats)
        rows.append((f"assignment_batch_B{B}_n{n}", us,
                     f"inst_per_s={B / us * 1e6:.1f};"
                     f"vmap_inst_per_s={B / us_v * 1e6:.1f};"
                     f"speedup_vs_vmap={us_v / us:.2f}x;"
                     f"mean_rounds={float(jnp.mean(res.rounds)):.0f}"))


@bench("sharded")
def bench_sharded(rows, repeats=2):
    """Batch-axis sharding over the device mesh: instances/sec vs devices.

    Run with emulated host devices to see >1 device on CPU:

        XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
            PYTHONPATH=src python -m benchmarks.run sharded

    Numbers land in benchmarks/RESULTS_sharded.md. dev=0 rows are the
    unsharded batched baseline (no shard_map in the dispatch).
    """
    import jax

    from repro.core.batch import stack_grid_problems
    from repro.core.maxflow.grid import GridProblem, maxflow_grid_batch
    from repro.core.maxflow.ref import random_grid_problem
    from repro.core.assignment.cost_scaling import solve_assignment
    from repro.launch.mesh import make_solver_mesh

    n_dev = len(jax.devices())
    counts = [c for c in (1, 2, 4, 8) if c <= n_dev]
    rng = np.random.default_rng(0)

    hw, B = 32, 32
    prob = stack_grid_problems(
        [GridProblem(*map(jnp.asarray, random_grid_problem(
            rng, hw, hw, max_cap=20, terminal_density=0.3)))
         for _ in range(B)])
    us0 = _time(maxflow_grid_batch, prob, reps=repeats)
    rows.append((f"maxflow_sharded_B{B}_{hw}x{hw}_dev0", us0,
                 f"inst_per_s={B / us0 * 1e6:.1f};unsharded_baseline"))
    for c in counts:
        mesh = make_solver_mesh(c)
        us = _time(maxflow_grid_batch, prob, mesh=mesh, reps=repeats)
        rows.append((f"maxflow_sharded_B{B}_{hw}x{hw}_dev{c}", us,
                     f"inst_per_s={B / us * 1e6:.1f};"
                     f"speedup_vs_unsharded={us0 / us:.2f}x"))

    n = 48
    ws = jnp.asarray(np.stack([
        np.random.default_rng(i).integers(0, 101, (n, n))
        for i in range(B)]), jnp.int32)
    us0 = _time(solve_assignment, ws, reps=repeats)
    rows.append((f"assignment_sharded_B{B}_n{n}_dev0", us0,
                 f"inst_per_s={B / us0 * 1e6:.1f};unsharded_baseline"))
    for c in counts:
        mesh = make_solver_mesh(c)
        us = _time(solve_assignment, ws, mesh=mesh, reps=repeats)
        rows.append((f"assignment_sharded_B{B}_n{n}_dev{c}", us,
                     f"inst_per_s={B / us * 1e6:.1f};"
                     f"speedup_vs_unsharded={us0 / us:.2f}x"))


@bench("compaction")
def bench_compaction(rows, repeats=2):
    """Early-exit compaction vs the masked baseline (instances/sec).

    A ragged-convergence batch — most instances converge within the first
    heuristic cycles, a few stragglers run long — is where the ROADMAP's
    compaction item pays: the masked path select-freezes converged
    instances but keeps computing full-batch cycles until the LAST
    straggler drains, while ``compact=True`` gathers the live instances
    into pow2-sized sub-batches so per-cycle FLOPs track the live count.
    Results are bit-identical (tests/test_compact.py); numbers land in
    benchmarks/RESULTS_compaction.md.
    """
    from repro.core.batch import stack_grid_problems
    from repro.core.maxflow.grid import GridProblem, maxflow_grid_batch
    from repro.core.maxflow.ref import random_grid_problem
    from repro.core.assignment.cost_scaling import solve_assignment

    rng = np.random.default_rng(0)
    hw, B, hard = 64, 32, 4
    probs = []
    for i in range(B):
        cap, cs, ct = random_grid_problem(rng, hw, hw, max_cap=20,
                                          terminal_density=0.3)
        if i >= hard:  # easy: almost no excess -> converge in early cycles
            cs = np.minimum(cs, 1.0)
        probs.append(GridProblem(*map(jnp.asarray, (cap, cs, ct))))
    prob = stack_grid_problems(probs)
    res = maxflow_grid_batch(prob)
    rag = (f"rounds_min={int(jnp.min(res.rounds))};"
           f"rounds_max={int(jnp.max(res.rounds))}")
    us_m = _time(maxflow_grid_batch, prob, reps=repeats)
    rows.append((f"maxflow_masked_B{B}_{hw}x{hw}", us_m,
                 f"inst_per_s={B / us_m * 1e6:.1f};{rag}"))
    us_c = _time(maxflow_grid_batch, prob, compact=True, reps=repeats)
    rows.append((f"maxflow_compact_B{B}_{hw}x{hw}", us_c,
                 f"inst_per_s={B / us_c * 1e6:.1f};"
                 f"speedup_vs_masked={us_m / us_c:.2f}x"))

    n = 64
    ws = np.stack([np.random.default_rng(i).integers(0, 101, (n, n))
                   for i in range(B)])
    ws[hard:] //= 25     # easy: small max|c| -> short eps schedules
    w = jnp.asarray(ws, jnp.int32)
    res = solve_assignment(w)
    rag = (f"rounds_min={int(jnp.min(res.rounds))};"
           f"rounds_max={int(jnp.max(res.rounds))}")
    us_m = _time(solve_assignment, w, reps=repeats)
    rows.append((f"assignment_masked_B{B}_n{n}", us_m,
                 f"inst_per_s={B / us_m * 1e6:.1f};{rag}"))
    us_c = _time(solve_assignment, w, compact=True, reps=repeats)
    rows.append((f"assignment_compact_B{B}_n{n}", us_c,
                 f"inst_per_s={B / us_c * 1e6:.1f};"
                 f"speedup_vs_masked={us_m / us_c:.2f}x"))


@bench("serving")
def bench_serving(rows, repeats=2):
    """Blocking-flush vs async-pipelined serving (throughput + latency).

    One recorded request stream — ragged-convergence grid cuts, the
    serving profile compaction pays on — is served three ways:

      * ``serving_blocking_flush`` — the PR-2 path: submit a chunk, call
        ``SolverEngine.flush()``, repeat. Host padding of chunk k+1 waits
        for the device solve of chunk k.
      * ``serving_async_masked`` — ``AsyncSolverEngine`` with the masked
        driver forced: size-triggered background flushes, host
        pad-and-bucket of batch k+1 overlapped with the device solve of
        batch k (double-buffered lanes).
      * ``serving_async_adaptive`` — adaptive dispatch on top: the
        convergence-spread EWMA flips ragged buckets to the compacted
        driver (dispatch counts in the derived column prove it).

    Derived columns report instances/sec and the async paths' p50/p99
    ticket latency (submit -> future resolution). Numbers land in
    benchmarks/RESULTS_serving.md (``python -m benchmarks.run serving``).
    """
    from repro.core.maxflow.grid import GridProblem
    from repro.core.maxflow.ref import random_grid_problem
    from repro.serve.engine import SolverEngine
    from repro.serve.scheduler import AsyncSolverEngine

    rng = np.random.default_rng(0)
    hw, B, chunk = 64, 32, 8
    probs = []
    for i in range(B):
        cap, cs, ct = random_grid_problem(rng, hw, hw, max_cap=20,
                                          terminal_density=0.3)
        if i % 4:   # 3 of 4 easy -> ragged convergence within every chunk
            cs = np.minimum(cs, 1.0)
        probs.append(GridProblem(*map(jnp.asarray, (cap, cs, ct))))

    def blocking():
        eng = SolverEngine(bucket="max")
        n = 0
        for lo in range(0, B, chunk):
            for p in probs[lo:lo + chunk]:
                eng.submit("maxflow", p)
            n += len(eng.flush())
        assert n == B

    def asynchronous(dispatch):
        metrics = None
        with AsyncSolverEngine(max_batch=chunk, max_delay_ms=10_000.0,
                               dispatch=dispatch, spread_threshold=0.15,
                               min_compact_batch=4) as eng:
            futs = [eng.submit("maxflow", p) for p in probs]
            for f in futs:
                f.result(timeout=600)
            metrics = eng.metrics
        return metrics

    blocking()                        # compile
    t0 = time.perf_counter()
    for _ in range(repeats):
        blocking()
    us_b = (time.perf_counter() - t0) / repeats * 1e6
    rows.append(("serving_blocking_flush", us_b,
                 f"inst_per_s={B / us_b * 1e6:.1f};chunks={B // chunk}"))

    for dispatch in ("masked", "adaptive"):
        asynchronous(dispatch)        # compile + warm the EWMA path
        t0 = time.perf_counter()
        for _ in range(repeats):
            m = asynchronous(dispatch)
        us_a = (time.perf_counter() - t0) / repeats * 1e6
        snap = m.snapshot()
        lat = snap["latency_ms"]
        extra = ""
        if dispatch == "adaptive":
            d = snap["dispatches"]
            extra = (f";masked_dispatches={d.get('maxflow:masked', 0)}"
                     f";compacted_dispatches="
                     f"{d.get('maxflow:compacted', 0)}")
        rows.append((f"serving_async_{dispatch}", us_a,
                     f"inst_per_s={B / us_a * 1e6:.1f};"
                     f"speedup_vs_blocking={us_b / us_a:.2f}x;"
                     f"p50_ms={lat['p50']:.1f};p99_ms={lat['p99']:.1f}"
                     + extra))


@bench("refill")
def bench_refill(rows, repeats=2):
    """Continuous batching vs closed batches on a ragged Poisson stream.

    The same recorded request stream — ragged-convergence grid cuts with
    Poisson inter-arrival gaps — is served twice through the async
    scheduler with the compacted driver forced:

      * ``refill_stream_closed`` — refill off: each flushed chunk drains
        as a closed compacted batch, so slots vacated by early-converging
        instances idle until the chunk's straggler finishes.
      * ``refill_stream_refill`` — ``refill=True``: freed slots are
        re-seeded from the pending queue at cycle boundaries
        (``repro.core.refill``), so the batch stays near capacity while
        requests keep arriving.

    The headline derived column is ``slot_occupancy``: mean live
    instances per compacted cycle over capacity (the closed path's
    ``compact_live_mean / max_batch``; the refill path's
    ``refill.utilization`` — the same per-cycle measure recorded by the
    session trace).  Steady-state occupancy must be strictly higher with
    refill; throughput and the admitted/session counts ride along.
    Numbers land in benchmarks/RESULTS_refill.md
    (``python -m benchmarks.run refill``).
    """
    from repro.core.maxflow.grid import GridProblem
    from repro.core.maxflow.ref import random_grid_problem
    from repro.serve.scheduler import AsyncSolverEngine

    rng = np.random.default_rng(0)
    hw, B, cap = 64, 48, 8
    probs = []
    for i in range(B):
        capn, cs, ct = random_grid_problem(rng, hw, hw, max_cap=20,
                                           terminal_density=0.3)
        if i % 4:   # 3 of 4 easy -> slots free early within every batch
            cs = np.minimum(cs, 1.0)
        probs.append(GridProblem(*map(jnp.asarray, (capn, cs, ct))))
    # one fixed Poisson arrival schedule, replayed identically both ways
    gaps = rng.exponential(0.002, B)

    def serve(refill):
        with AsyncSolverEngine(max_batch=cap, max_delay_ms=20.0,
                               dispatch="compacted", refill=refill,
                               n_lanes=2) as eng:
            futs = []
            for p, gap in zip(probs, gaps):
                time.sleep(gap)
                futs.append(eng.submit("maxflow", p))
            for f in futs:
                f.result(timeout=600)
            return eng.metrics.snapshot()

    results = {}
    for name, refill in (("closed", False), ("refill", True)):
        serve(refill)                 # compile
        t0 = time.perf_counter()
        for _ in range(repeats):
            snap = serve(refill)
        us = (time.perf_counter() - t0) / repeats * 1e6
        if refill:
            occ = snap["refill"]["utilization"]
            extra = (f";admitted="
                     f"{sum(snap['refill']['admitted'].values())}"
                     f";sessions="
                     f"{sum(snap['refill']['sessions'].values())}")
        else:
            occ = snap["compact_live_mean"] / cap
            extra = ""
        results[name] = (us, occ)
        rows.append((f"refill_stream_{name}", us,
                     f"inst_per_s={B / us * 1e6:.1f};"
                     f"slot_occupancy={occ:.3f}" + extra))
    us_c, occ_c = results["closed"]
    us_r, occ_r = results["refill"]
    rows.append(("refill_stream_gain", us_c - us_r,
                 f"occupancy_gain={occ_r / occ_c:.2f}x;"
                 f"speedup_vs_closed={us_c / us_r:.2f}x"))


@bench("assignment", kind="assignment")
def bench_assignment(rows, repeats=2):
    """Paper §6: n<=30, costs<=100, ~1/20 s on a GTX 560 Ti."""
    from repro.core.assignment.cost_scaling import solve_assignment
    rng = np.random.default_rng(0)
    for n in (10, 30, 64, 128, 256):
        w = jnp.asarray(rng.integers(0, 101, (n, n)), jnp.int32)
        for method in ("pushrelabel", "auction"):
            res = solve_assignment(w, method=method)
            us = _time(solve_assignment, w, method=method, reps=repeats)
            note = ""
            if n == 30:
                note = f";paper_50000us_speedup={50_000/us:.1f}x"
            rows.append((f"assignment_{method}_n{n}", us, int(res.rounds),
                         f"ops={int(res.pushes)+int(res.relabels)}" + note))


@bench("matching", kind="matching")
def bench_matching(rows, repeats=2):
    """Bipartite maximum-cardinality matching (BFS augmenting rounds).

    Single-instance sizes vs the host Hopcroft-Karp oracle (the device
    path must match its cardinality exactly — asserted, not just timed),
    then the batched masked/compacted drivers, then the Pallas frontier
    backend end-to-end (interpreted off-TPU: correctness-scale timing)."""
    from repro.core.matching import match_bipartite, match_bipartite_batch
    from repro.kernels import interpret_mode
    from repro.core.matching.ref import hopcroft_karp, random_bipartite
    rng = np.random.default_rng(0)
    for n in (64, 128, 256):
        adj = jnp.asarray(random_bipartite(rng, n, n, p=4.0 / n))
        res = match_bipartite(adj)
        us = _time(match_bipartite, adj, reps=repeats)
        t0 = time.perf_counter()
        hk_card = hopcroft_karp(np.asarray(adj))[2]
        hk_us = (time.perf_counter() - t0) * 1e6
        assert int(res.cardinality) == int(hk_card)
        rows.append((f"matching_{n}x{n}", us, int(res.rounds),
                     f"card={int(res.cardinality)};hk_host_us={hk_us:.0f}"))
    B, n = 32, 64
    adjs = jnp.asarray(np.stack(
        [random_bipartite(rng, n, n, p=6.0 / n) for _ in range(B)]))
    res = match_bipartite_batch(adjs)
    us_m = _time(match_bipartite_batch, adjs, reps=repeats)
    rows.append((f"matching_masked_B{B}_n{n}", us_m,
                 f"inst_per_s={B / us_m * 1e6:.1f};"
                 f"rounds_min={int(jnp.min(res.rounds))};"
                 f"rounds_max={int(jnp.max(res.rounds))}"))
    us_c = _time(match_bipartite_batch, adjs, compact=True, reps=repeats)
    rows.append((f"matching_compact_B{B}_n{n}", us_c,
                 f"inst_per_s={B / us_c * 1e6:.1f};"
                 f"speedup_vs_masked={us_m / us_c:.2f}x"))
    adj32 = jnp.asarray(random_bipartite(rng, 32, 32, p=0.15))
    us_x = _time(match_bipartite, adj32, reps=repeats)
    us_p = _time(match_bipartite, adj32, backend="pallas", reps=repeats)
    mode = "_interp" if interpret_mode() else ""
    rows.append((f"matching_pallas{mode}_32x32", us_p, f"xla_us={us_x:.0f}"))


@bench("warmstart")
def bench_warmstart(rows, repeats=2):
    """Incremental re-solve: cold vs warm-started maxflow on an edit chain.

    A batch of grid instances is solved once, then mutated ``steps`` times
    (a few terminal-capacity edits per step — the docs/warmstart.md
    streaming pattern).  Each step is re-solved two ways on the SAME
    mutated problems:

      * ``warmstart_cold`` — from scratch through ``solve_batch``;
      * ``warmstart_warm`` — through ``solve_warm`` seeded with the
        previous step's solution (``WarmStart(sol, base_problem=prev)``).

    Warm and cold flows must bit-match (asserted — this bench doubles as
    an end-to-end equivalence check).  The headline numbers are the total
    push-relabel rounds down each chain: warm must spend strictly fewer.
    Numbers land in benchmarks/RESULTS_warmstart.md
    (``python -m benchmarks.run warmstart``).
    """
    from repro.core.batch import solve_batch
    from repro.core.kinds import get_kind
    from repro.core.maxflow.grid import GridProblem
    from repro.core.maxflow.ref import random_grid_problem
    from repro.core.warm import WarmStart, solve_warm

    rng = np.random.default_rng(0)
    kind = get_kind("maxflow")
    B, hw, steps = 4, 32, 3
    bases = []
    for _ in range(B):
        cap, cs, ct = random_grid_problem(rng, hw, hw, max_cap=20,
                                          terminal_density=0.3)
        bases.append(GridProblem(*map(jnp.asarray, (cap, cs, ct))))

    def mutate(p):
        # sparse terminal edits: the incremental-serving workload shape
        cs = np.asarray(p.cap_src).copy()
        ct = np.asarray(p.cap_sink).copy()
        for arr in (cs, ct):
            mask = rng.random(arr.shape) < 0.01
            arr[mask] = np.maximum(
                arr[mask] + rng.integers(-3, 4, int(mask.sum())), 0)
        return GridProblem(p.cap_nbr, *map(jnp.asarray, (cs, ct)))

    chains = [[p := b] + [p := mutate(p) for _ in range(steps)]
              for b in bases]

    def run_cold():
        rounds = 0
        res = None
        for s in range(1, steps + 1):
            res = solve_batch("maxflow", [c[s] for c in chains])
            rounds += sum(int(r.rounds) for r in res)
        return res, rounds

    base_res = solve_batch("maxflow", [c[0] for c in chains])

    def run_warm():
        # the base solve is shared state both paths already hold; only the
        # `steps` re-solves are timed, for warm and cold alike
        prev = base_res
        rounds = 0
        res = None
        for s in range(1, steps + 1):
            warm = {i: WarmStart(kind.solution_of(prev[i]),
                                 base_problem=chains[i][s - 1])
                    for i in range(B)}
            res = solve_warm("maxflow", [c[s] for c in chains], warm)
            rounds += sum(int(r.rounds) for r in res)
            prev = res
        return res, rounds

    (cold_res, cold_rounds), _ = run_cold(), run_warm()  # compile
    t0 = time.perf_counter()
    for _ in range(repeats):
        cold_res, cold_rounds = run_cold()
    us_c = (time.perf_counter() - t0) / repeats * 1e6
    t0 = time.perf_counter()
    for _ in range(repeats):
        warm_res, warm_rounds = run_warm()
    us_w = (time.perf_counter() - t0) / repeats * 1e6

    for a, b in zip(cold_res, warm_res):
        assert float(a.flow) == float(b.flow), "warm != cold optimum"
    assert warm_rounds < cold_rounds, (warm_rounds, cold_rounds)
    rows.append(("warmstart_cold", us_c, cold_rounds,
                 f"B={B};hw={hw};steps={steps};"
                 f"flow_sum={sum(float(r.flow) for r in cold_res):.0f}"))
    rows.append(("warmstart_warm", us_w, warm_rounds,
                 f"rounds_saved={cold_rounds - warm_rounds}"))
    rows.append(("warmstart_gain", us_c - us_w,
                 f"rounds_ratio={cold_rounds / max(warm_rounds, 1):.2f}x;"
                 f"wall_speedup={us_c / us_w:.2f}x"))


@bench("refine_ops")
def bench_refine_ops(rows, repeats=2):
    """Operation-count scaling (the paper analyzes O(n^2 m) op bounds)."""
    from repro.core.assignment.cost_scaling import solve_assignment
    rng = np.random.default_rng(1)
    prev = None
    for n in (16, 32, 64, 128):
        w = jnp.asarray(rng.integers(0, 101, (n, n)), jnp.int32)
        res = solve_assignment(w, method="pushrelabel")
        ops = int(res.pushes) + int(res.relabels)
        growth = f";growth={ops/prev:.2f}x" if prev else ""
        prev = ops
        rows.append((f"refine_ops_n{n}", float(ops),
                     f"bound_n2m={n**2 * n * n}" + growth))


@bench("routing")
def bench_routing(rows, repeats=2):
    """Flow router vs top-k: drops, balance, overhead (MoE integration)."""
    from repro.core.routing import auction_route, topk_route
    rng = np.random.default_rng(0)
    T, E, k = 4096, 16, 2
    cap = int(T * k / E * 1.25)
    s = jnp.asarray(rng.normal(size=(T, E)).astype(np.float32))
    s = s.at[:, 0].add(2.0)  # hot expert
    for name, fn in (("topk", topk_route), ("flow", auction_route)):
        r = fn(s, k, cap)
        us = _time(fn, s, k, cap, reps=repeats)
        d = np.asarray(r.dispatch)
        load = d.sum(0)
        rows.append((f"route_{name}_T{T}_E{E}", us,
                     f"dropped={T*k - int(d.sum())};"
                     f"load_cv={load.std()/load.mean():.3f}"))


@bench("kernels")
def bench_kernels(rows, repeats=2):
    """Bidding kernel tile sweep (compiled on a TPU, interpreted
    elsewhere: correctness-scale)."""
    from repro.kernels import interpret_mode
    from repro.kernels.bidding.kernel import bidding
    from repro.kernels.bidding.ref import bidding_ref
    rng = np.random.default_rng(0)
    n = 512
    c = jnp.asarray(rng.integers(-1000, 1000, (n, n)), jnp.int32)
    p = jnp.asarray(rng.integers(-500, 500, (n,)), jnp.int32)
    m = jnp.asarray(rng.random((n, n)) < 0.3)
    interp = interpret_mode()
    mode = "_interp" if interp else ""
    us_ref = _time(bidding_ref, c, p, m, reps=repeats)
    rows.append((f"bidding_ref_xla_n{n}", us_ref, "oracle"))
    for br, bc in ((128, 128), (256, 256), (256, 512)):
        vmem_kib = (br * bc * 5 + bc * 4 + br * 12) / 1024
        us = _time(bidding, c, p, m, block_rows=br, block_cols=bc,
                   interpret=interp, reps=repeats)
        rows.append((f"bidding_kernel_{br}x{bc}{mode}", us,
                     f"vmem_per_step_KiB={vmem_kib:.0f}"))


@bench("flash")
def bench_flash_kernel(rows, repeats=2):
    """Flash-attention Pallas kernel vs jnp flash path (compiled on a TPU,
    interpreted elsewhere)."""
    from repro.kernels import interpret_mode
    from repro.kernels.flash_attention.kernel import flash_attention_fwd
    from repro.kernels.flash_attention.ref import flash_attention_ref
    import numpy as np
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    B, S, H, KV, dh = 1, 512, 4, 2, 64
    q = jnp.asarray(rng.normal(size=(B, S, H, dh)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, KV, dh)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, KV, dh)).astype(np.float32))
    interp = interpret_mode()
    mode = "_interp" if interp else ""
    us_ref = _time(flash_attention_ref, q, k, v, reps=repeats)
    rows.append((f"flash_ref_xla_S{S}", us_ref, "dense oracle"))
    for bq, bk in ((128, 128), (256, 512)):
        vmem = (bq * dh + 2 * bk * dh + bq * bk + bq * (dh + 2)) * 4 / 1024
        us = _time(flash_attention_fwd, q, k, v, block_q=bq, block_k=bk,
                   interpret=interp, reps=repeats)
        rows.append((f"flash_kernel_{bq}x{bk}{mode}", us,
                     f"vmem_per_step_KiB={vmem:.0f}"))
