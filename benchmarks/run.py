"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,rounds,wall_s,derived`` CSV (plus a trailing
roofline pointer: the dry-run roofline table lives in EXPERIMENTS.md and
results/dryrun_*.json). ``rounds`` is the solver's per-instance round
count — the machine-independent cost measure (wall-clock on the CPU CI
runner says little about TPU behaviour; round counts transfer). Benches
append either ``(name, us, rounds, derived)`` or the legacy 3-tuple
``(name, us, derived)`` (rounds column left empty); ``wall_s`` is filled
in by the harness — wall-clock seconds from the previous row (or the
bench function's start) to this row's append, so the column sums to the
total harness runtime and exposes which measurement (compile + timing,
not just the timed calls) actually dominates a CI run.

Usage::

    python -m benchmarks.run [bench] [--repeats N] [--csv PATH]
                             [--trace PATH]

``--trace PATH`` installs an ambient ``repro.obs.Tracer`` around the
whole run and saves it as Chrome-trace JSON (open in Perfetto /
``chrome://tracing``). Engines the benches construct capture the ambient
tracer, so the serving benches emit full per-request lifecycle spans —
CI uploads the serving bench's trace as an artifact.

The bench table is not hardcoded here: ``benchmarks.bench_flow`` registers
each benchmark with the ``@bench(name, kind=...)`` decorator and this
harness enumerates that registry. Benches tied to a solver kind are
cross-checked against ``repro.core.kinds.registered_kinds()`` — registering
a new solver kind without a benchmark makes every ``benchmarks.run``
invocation fail loudly instead of silently shipping the kind unmeasured.

Run as a script, it keeps JAX's persistent compilation cache in
``JAX_COMPILATION_CACHE_DIR`` when that is set, else in the checkout's
``.jax_cache`` (``repro.launch.compile_cache``).

Unknown bench names are rejected with the list of available benches
(previously they silently printed an empty CSV). ``--csv PATH`` writes the
same CSV to a file so callers (CI's artifact step) don't have to depend on
shell redirection or the current working directory.
"""
from __future__ import annotations

import argparse
import contextlib
import pathlib
import time

from benchmarks.bench_flow import BENCHES, KIND_BENCHES


class _TimedRows(list):
    """Row sink that stamps wall-clock time at every ``append``.

    Benches are unaware of the ``wall_s`` column: they keep appending
    3/4-tuples and the harness derives per-row wall seconds from the
    append timestamps (delta from the previous append, or from ``mark()``
    at the start of the bench function for its first row).
    """

    def __init__(self):
        super().__init__()
        self.stamps: list[float] = []
        self._prev = time.monotonic()

    def mark(self) -> None:
        self._prev = time.monotonic()

    def append(self, row) -> None:
        now = time.monotonic()
        self.stamps.append(now - self._prev)
        self._prev = now
        super().append(row)


def _check_kind_coverage() -> None:
    """Every registered solver kind must have a bench tied to it."""
    from repro.core.kinds import registered_kinds
    missing = [k for k in registered_kinds() if k not in KIND_BENCHES]
    if missing:
        raise SystemExit(
            f"solver kinds without a benchmark: {', '.join(missing)} — "
            f"tie one in with @bench(name, kind=...) in "
            f"benchmarks/bench_flow.py")


def _check_row(row) -> None:
    """A bench row must lower cleanly into the CSV schema — fail FAST,
    naming the offending bench and row, instead of emitting a ragged line
    that downstream artifact parsing half-reads.

    Accepted shapes: ``(name, us, rounds, derived)`` or the legacy
    ``(name, us, derived)``; ``name`` a non-empty string without commas
    or newlines (it is a CSV cell), ``us`` a finite number, ``rounds`` an
    integer-valued number or ``None``.
    """
    def die(why: str):
        raise SystemExit(f"malformed bench row {row!r}: {why} — every row "
                         f"must match name,us_per_call,rounds,wall_s,derived")
    if not isinstance(row, tuple) or len(row) not in (3, 4):
        die("expected a (name, us, rounds, derived) or (name, us, derived) "
            "tuple")
    name, us = row[0], row[1]
    rounds = row[2] if len(row) == 4 else None
    if not isinstance(name, str) or not name or "," in name or "\n" in name:
        die("name must be a non-empty string without commas/newlines")
    try:
        us = float(us)
    except (TypeError, ValueError):
        die(f"us_per_call {us!r} is not a number")
    if us != us or us in (float("inf"), float("-inf")):
        die(f"us_per_call {us!r} is not finite")
    if rounds is not None:
        try:
            int(rounds)
        except (TypeError, ValueError):
            die(f"rounds {rounds!r} is not an integer count")


def main(argv: list[str] | None = None) -> None:
    parser = argparse.ArgumentParser(
        prog="python -m benchmarks.run",
        description="Run one benchmark (or all) and print CSV to stdout.")
    parser.add_argument(
        "bench", nargs="?", choices=sorted(BENCHES), metavar="bench",
        help=f"which benchmark to run (default: all). "
             f"Available: {', '.join(sorted(BENCHES))}")
    parser.add_argument(
        "--repeats", type=int, default=2,
        help="timed repetitions per measurement after the compile call "
             "(default: %(default)s)")
    parser.add_argument(
        "--csv", type=pathlib.Path, default=None, metavar="PATH",
        help="also write the CSV to PATH (parent dirs created; output is "
             "still printed to stdout)")
    parser.add_argument(
        "--trace", type=pathlib.Path, default=None, metavar="PATH",
        help="record a repro.obs trace of the whole run and save it as "
             "Chrome-trace JSON at PATH (open in Perfetto)")
    args = parser.parse_args(argv)
    _check_kind_coverage()

    tracer = None
    trace_ctx: contextlib.AbstractContextManager = contextlib.nullcontext()
    if args.trace is not None:
        from repro.obs.trace import Tracer, use_tracer
        tracer = Tracer()
        trace_ctx = use_tracer(tracer)

    rows = _TimedRows()
    with trace_ctx:
        for name, fn in BENCHES.items():
            if args.bench and args.bench != name:
                continue
            rows.mark()
            if tracer is not None:
                t0 = time.monotonic()
                fn(rows, repeats=args.repeats)
                tracer.record("bench", t0, time.monotonic(), bench=name)
            else:
                fn(rows, repeats=args.repeats)
    lines = ["name,us_per_call,rounds,wall_s,derived"]
    for row, wall in zip(rows, rows.stamps):
        _check_row(row)
        if len(row) == 4:
            name, us, rounds, derived = row
            r = "" if rounds is None else str(int(rounds))
        else:
            name, us, derived = row
            r = ""
        lines.append(f"{name},{us:.1f},{r},{wall:.3f},{derived}")
    print("\n".join(lines))
    if args.csv is not None:
        args.csv.parent.mkdir(parents=True, exist_ok=True)
        args.csv.write_text("\n".join(lines) + "\n")
    if tracer is not None:
        args.trace.parent.mkdir(parents=True, exist_ok=True)
        tracer.save(args.trace)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache(pathlib.Path(__file__).resolve().parents[1]
                      / ".jax_cache")
    main()
