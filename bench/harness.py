"""One run of one benchmark cell: set-up, measured window, check, result.

Everything a cell needs is found by name, so a new cell, configuration,
traffic mix or per-layer metric is new files plus new entries:

* the cell, its configuration and the metrics in ``BENCHMARK.json``;
* the configuration's file (sizes, generator, reference, solver settings);
* the traffic mix, ``bench/traffic/<mix>.json``, read by ``closed_loop``
  or ``open_loop`` below, whichever its ``loop`` names;
* the generator, ``bench/gen/<name>.py``, and the plain reference,
  ``bench/reference/<name>.py``, named by the configuration;
* per-layer readers, ``bench/metrics/<metric>.py``, each ``read(record)``
  returning a number or ``None`` (then the metric is left out of the line);
* the kernels a trace is searched for, ``bench/kernels/<kernel>.py``.

The program under test is driven only through its public entry points:
``repro.core.solve_batch`` (closed loop) and
``repro.serve.scheduler.AsyncSolverEngine.submit`` (open loop).
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import multiprocessing
import os
import pathlib
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import ProcessPoolExecutor

import numpy as np

BENCH_DIR = "bench"
GRACE_S = 60.0          # an open-loop answer may come this late past close
REF_WORKERS = 4         # processes that run the plain reference at the end


# ------------------------------------------------------------ the cell

@dataclasses.dataclass
class Cell:
    root: pathlib.Path          # checkout root (holds BENCHMARK.json)
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list            # BENCHMARK.json metric entries of this cell
    per_layer: list

    @property
    def bench(self) -> pathlib.Path:
        return self.root / BENCH_DIR


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root, workload: str) -> Cell:
    root = pathlib.Path(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; cells: "
                         f"{', '.join(sorted(cells))}")
    w = cells[workload]
    [centry] = [c for c in spec["configs"] if c["name"] == w["config"]]
    e2e = [m for m in spec["end_to_end"] if _reports(m, workload)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if m["moves"] in e2e_names and _reports(m, workload)]
    traffic_file = root / BENCH_DIR / "traffic" / f"{w['traffic']}.json"
    return Cell(root=root, name=workload, chips=int(w["chips"]),
                config=json.loads((root / centry["file"]).read_text()),
                traffic=json.loads(traffic_file.read_text()),
                end_to_end=e2e, per_layer=per_layer)


_MODULES: dict = {}


def load_module(path):
    """Import a benchmark file by path (names may hold dots)."""
    path = pathlib.Path(path).resolve()
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            "bench_file_" + str(len(_MODULES)), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def part(cell: Cell, kind: str, name: str):
    """``bench/<kind>/<name>.py`` of this cell's checkout."""
    return load_module(cell.bench / kind / f"{name}.py")


def use_compile_cache(default_dir) -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` or a fixed
    directory in the checkout; every program is cached, however small."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(pathlib.Path(default_dir).resolve())
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache while
    ``on`` (a program found in memory counts nothing)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring
        self.on = False
        self.count = 0
        self._mon = jax.monitoring
        self._mon.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **kw):
        if self.on and event in self.EVENTS:
            self.count += 1

    def close(self):
        self._mon.unregister_event_duration_listener(self._listen)


# ------------------------------------------------------------ traffic

def order_stream(rng: np.random.Generator, n: int):
    """Pool indices: every pool member once per seeded permutation."""
    while True:
        yield from (int(i) for i in rng.permutation(n))


def arrival_offsets(rng: np.random.Generator, rate: float,
                    seconds: float) -> np.ndarray:
    """Open-loop due times in ``(0, seconds]``.

    ``round(rate * seconds)`` arrivals whose gaps are the exponential
    distribution's quantiles at ``(i + 1/2) / n``, in the order ``rng``
    draws, scaled to fill the window: Poisson-shaped gaps.
    """
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps = rng.permutation(gaps)
    return np.cumsum(gaps) * (seconds / gaps.sum())


# ------------------------------------------------------------ loops

@dataclasses.dataclass
class Window:
    seconds: float            # measured window length
    attempted: int
    completed: int            # answers returned inside the window
    failed: int
    kept: list                # (pool index, {field: device value}) kept
    rounds: list              # device scalars, one per answer
    latencies_ms: list | None = None
    lateness_ms: list | None = None
    spans: list | None = None
    t0: float = 0.0           # window start, time.monotonic()
    due_ms: list | None = None    # open loop: each request's due time
    order: list | None = None     # open loop: each request's pool index


def _keep(res, fields):
    return {f: getattr(res, f) for f in fields}


def closed_loop(cell: Cell, pool, rngs, seconds, fields, check_ids,
                solver_kw, annotate) -> Window:
    """One ``solve_batch`` call at a time, each on the next pool draws."""
    import jax
    from repro.core import solve_batch
    kind = cell.config["kind"]
    mesh = None
    batch = int(cell.config["batch"])
    if cell.traffic.get("shard"):
        from repro.launch.mesh import make_solver_mesh
        mesh = make_solver_mesh(cell.chips)
        batch *= cell.chips
    warm = [pool[i % len(pool)] for i in range(batch)]
    jax.block_until_ready(solve_batch(kind, warm, mesh=mesh, **solver_kw))

    def window():
        order = order_stream(rngs["order"], len(pool))
        kept, rounds, n = [], [], 0
        t0 = time.monotonic()
        t_end = t0 + seconds
        with annotate("bench:window"):
            while True:
                idx = [next(order) for _ in range(batch)]
                with annotate("bench:solve_batch"):
                    res = solve_batch(kind, [pool[i] for i in idx],
                                      mesh=mesh, **solver_kw)
                    jax.block_until_ready(res)
                for i, r in zip(idx, res):
                    rounds.append(r.rounds)
                    if i in check_ids:
                        kept.append((i, _keep(r, fields)))
                n += batch
                now = time.monotonic()
                if now >= t_end:
                    break
        return Window(seconds=now - t0, attempted=n, completed=n, failed=0,
                      kept=kept, rounds=rounds, t0=t0)
    return window


def open_loop(cell: Cell, pool, rngs, seconds, fields, check_ids,
              solver_kw, annotate, tracer) -> tuple:
    """Open-loop arrivals of single instances into ``AsyncSolverEngine``.

    Returns ``(window, close)``. Latency runs from when a request was
    due to when its future resolved; lateness is how late the generator
    submitted it.
    """
    from repro.serve.scheduler import AsyncSolverEngine
    kind = cell.config["kind"]
    eng = AsyncSolverEngine(**cell.traffic["engine"],
                            solver_kw={kind: solver_kw}, tracer=tracer)
    # warm every batch size the window can form: one program each
    for k in range(1, int(cell.traffic["engine"]["max_batch"]) + 1):
        futs = [eng.submit(kind, pool[i % len(pool)], deadline_ms=600_000)
                for i in range(k)]
        eng.flush_now()
        for f in futs:
            f.result(timeout=600)
    if tracer is not None:
        tracer.clear()

    def window():
        # the schedule is the traffic mix's own, the same in every run; the
        # seed picks which instance arrives when
        offs = arrival_offsets(
            np.random.default_rng(cell.traffic["schedule_seed"]),
            cell.traffic["rate_per_s"], seconds)
        order = order_stream(rngs["order"], len(pool))
        n = len(offs)
        done = [None] * n
        marked = [threading.Event() for _ in range(n)]
        late = [0.0] * n
        futs, idx = [], []

        def mark(i, fut):
            # a future's waiters wake before its callbacks run: wait for this
            done[i] = time.monotonic()
            marked[i].set()

        t0 = time.monotonic()
        with annotate("bench:window"):
            for i in range(n):
                due = t0 + offs[i]
                delay = due - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
                late[i] = time.monotonic() - due
                j = next(order)
                f = eng.submit(kind, pool[j])
                f.add_done_callback(functools.partial(mark, i))
                futs.append(f)
                idx.append(j)
            delay = t0 + seconds - time.monotonic()
            if delay > 0:
                time.sleep(delay)
        t_close = t0 + seconds
        kept, rounds, failed, lat = [], [], 0, []
        for i, (j, f) in enumerate(zip(idx, futs)):
            try:
                if not marked[i].wait(max(0.0, t_close + GRACE_S
                                          - time.monotonic())):
                    raise TimeoutError(f"request {i} never resolved")
                r = f.result(timeout=0)
            except Exception:
                failed += 1
                lat.append((t_close + GRACE_S - t0 - offs[i]) * 1e3)
                continue
            lat.append((done[i] - t0 - offs[i]) * 1e3)
            rounds.append(r.rounds)
            if j in check_ids:
                kept.append((j, _keep(r, fields)))
        completed = sum(1 for d in done if d is not None and d <= t_close)
        spans = None
        if tracer is not None:
            spans = [dict(name=s.name, t0=s.t0, t1=s.t1, attrs=s.attrs)
                     for s in tracer.spans()]
        return Window(seconds=seconds, attempted=n, completed=completed,
                      failed=failed, kept=kept, rounds=rounds,
                      latencies_ms=lat, lateness_ms=[x * 1e3 for x in late],
                      spans=spans, t0=t0, due_ms=[x * 1e3 for x in offs],
                      order=idx)
    return window, eng.close


# ------------------------------------------------------------ the check

def _ref_worker(path, instance):
    return load_module(path).solve(instance)


def reference_answers(ref_path, instances: dict) -> dict:
    """``{pool index: reference answer}``, computed in worker processes
    (the references hold the interpreter lock)."""
    if not instances:
        return {}
    ctx = multiprocessing.get_context("spawn")
    keys = list(instances)
    with ProcessPoolExecutor(min(REF_WORKERS, len(keys)),
                             mp_context=ctx) as ex:
        out = ex.map(_ref_worker, [str(ref_path)] * len(keys),
                     [instances[k] for k in keys])
        return dict(zip(keys, out))


def check(ref_mod, ref_path, pool, check_ids, kept) -> dict:
    """Every kept answer against the reference: ``{number: (value,
    limit)}``. A checked pool member with no answer counts as missing."""
    import jax
    refs = reference_answers(ref_path, {i: pool[i] for i in check_ids})
    values: dict = {k: 0 for k in ref_mod.LIMITS}
    answered = set()
    for i, leaves in kept:
        answer = {k: np.asarray(jax.device_get(v)) for k, v in leaves.items()}
        for k, v in ref_mod.compare(pool[i], answer, refs[i]).items():
            how = ref_mod.LIMITS[k][1]
            values[k] = max(values[k], v) if how == "max" else values[k] + v
        answered.add(i)
    out = {k: (values[k], lim) for k, (lim, _) in ref_mod.LIMITS.items()}
    out["missing"] = (len(set(check_ids) - answered), 0)
    return out


# ------------------------------------------------------------ reduction

def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, float), q))


def end_to_end(cell: Cell, win: Window, setup_s: float) -> dict:
    units = {m["name"]: m["unit"] for m in cell.end_to_end}
    val = {"setup_s": setup_s,
           "inst_per_s": win.completed / win.seconds}
    if win.latencies_ms is not None:
        val["p50_ms"] = percentile(win.latencies_ms, 50)
        val["p95_ms"] = percentile(win.latencies_ms, 95)
    return {k: {"value": val[k], "unit": u} for k, u in units.items()
            if k in val}


def per_layer(cell: Cell, record: dict) -> dict:
    out = {}
    for m in cell.per_layer:
        v = part(cell, "metrics", m["name"]).read(record)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def device_info(devices, n_used: int) -> dict:
    peaks = []
    for d in devices[:n_used]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


# ------------------------------------------------------------ one run

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, control: bool = False, log=None,
             dump=None) -> dict:
    """Set up, measure, check; returns the result object. ``dump``, a
    path, receives every request's due time, latency and lateness."""
    import contextlib
    import jax
    from bench import trace as trace_mod
    log = log or (lambda *a: print(*a, flush=True))
    cfg = cell.config
    ss = np.random.SeedSequence(seed % 2 ** 64)
    rngs = dict(zip(("pool", "order", "check"),
                    (np.random.default_rng(s) for s in ss.spawn(3))))
    pool = part(cell, "gen", cfg["generator"]).pool(rngs["pool"],
                                                    cfg["sizes"])
    ref_path = cell.bench / "reference" / f"{cfg['reference']}.py"
    ref_mod = load_module(ref_path)
    check_ids = set(int(i) for i in rngs["check"].choice(
        len(pool), size=min(int(cfg["check_instances"]), len(pool)),
        replace=False))
    solver_kw = dict(cfg["solver_kw"])
    if control:
        solver_kw.update(cfg["control"]["solver_kw"])

    tracer = None
    if trace:
        from jax.profiler import TraceAnnotation as annotate
        if cell.traffic["loop"] == "open":
            from repro.obs import Tracer
            tracer = Tracer()
    else:
        annotate = lambda name: contextlib.nullcontext()   # noqa: E731

    close = lambda: None                                   # noqa: E731
    if cell.traffic["loop"] == "closed":
        window = closed_loop(cell, pool, rngs, seconds, ref_mod.FIELDS,
                             check_ids, solver_kw, annotate)
    elif cell.traffic["loop"] == "open":
        window, close = open_loop(cell, pool, rngs, seconds, ref_mod.FIELDS,
                                  check_ids, solver_kw, annotate, tracer)
    else:
        raise ValueError(f"traffic loop {cell.traffic['loop']!r}")
    setup_s = time.monotonic() - t_start

    counter = CompileCounter()
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # no event per Python call
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        counter.on = True
        try:
            win = window()
        finally:
            counter.on = False
            if trace:
                t_stop = time.monotonic()
                jax.profiler.stop_trace()
                t_stop = time.monotonic() - t_stop
        close()
        devices = jax.devices()
        dev = device_info(devices, cell.chips)
        reduced = None
        if trace:
            t_reduce = time.monotonic()
            kernels = {p.stem: load_module(p).MATCH
                       for p in sorted((cell.bench / "kernels").glob("*.py"))
                       if p.stem != "__init__"}
            reduced = trace_mod.reduce(trace_mod.find_xplane(trace_dir),
                                       kernels=kernels,
                                       host_spans=win.spans,
                                       window_t0=win.t0,
                                       n_chips=cell.chips)
            log(f"[trace] stop_s={t_stop} "
                f"reduce_s={time.monotonic() - t_reduce}")
    finally:
        counter.close()
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    rounds = [float(r) for r in jax.device_get(win.rounds)]

    late = win.lateness_ms or [0.0]
    lat = win.latencies_ms or [0.0]
    log(f"[window] seconds={win.seconds} attempted={win.attempted} "
        f"completed={win.completed} failed={win.failed} "
        f"latency_p95_ms={percentile(lat, 95)} "
        f"compiles_in_window={counter.count} "
        f"generator_late_p95_ms={percentile(late, 95)} "
        f"generator_late_max_ms={max(late)} "
        f"memory_peak_bytes={dev['memory_peak_bytes']} setup_s={setup_s}")

    if dump and win.latencies_ms is not None:
        pathlib.Path(dump).write_text(json.dumps(dict(
            seed=seed, setup_s=setup_s, due_ms=win.due_ms,
            latency_ms=win.latencies_ms, late_ms=win.lateness_ms,
            order=win.order)))

    t_check = time.monotonic()
    checks = check(ref_mod, ref_path, pool, sorted(check_ids), win.kept)
    checks["failed"] = (win.failed, 0)      # answers that never came
    log(f"[check] seconds={time.monotonic() - t_check} "
        f"instances={len(check_ids)} answers={len(win.kept)}")

    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": win.attempted, "failed": win.failed}
    if trace:
        record = dict(cell=cell, device=reduced, spans=win.spans,
                      rounds=rounds, latencies_ms=win.latencies_ms)
        result["metrics"] = per_layer(cell, record)
        dev.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["device"] = dev
        result["breakdown"] = {"device_ops": reduced["top_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        result["metrics"] = end_to_end(cell, win, setup_s)
        result["device"] = dev
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def emit(result: dict) -> None:
    """The numbers compared, last on standard error; the result line, last
    on standard output."""
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
