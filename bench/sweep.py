#!/usr/bin/env python3
"""Find where an open-loop cell's rate stops holding: a sweep of fixed rates.

    python3 bench/sweep.py --workload grid_cut_512.served --rates 4,8,12 --seconds 20

One process on the chip, the cell's engine settings, one window per rate.
For each rate it prints the latency p50 and p95, the p95 of the requests
due in the window's first and last quarters (a last quarter far above the
first means a backlog that grows through the window) and how long the
queue took to drain after the window closed. The served cells' rates are
fixed from one such sweep; the benchmark's own runs never search.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import numpy as np
    from bench import harness
    cell = harness.load_cell(ROOT, args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    harness.use_compile_cache(ROOT / ".jax_cache")
    pool = harness.part(cell, "gen", cell.config["generator"]).pool(
        np.random.default_rng(args.seed), cell.config["sizes"])
    for rate in (float(r) for r in args.rates.split(",")):
        c = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                   rate_per_s=rate))
        rngs = {"order": np.random.default_rng(args.seed)}
        window, close = harness.open_loop(
            c, pool, rngs, args.seconds, (), set(), cell.config["solver_kw"],
            lambda name: __import__("contextlib").nullcontext(), None)
        win = window()
        t_drained = time.monotonic()
        close()
        lat = np.asarray(win.latencies_ms)
        q = max(1, len(lat) // 4)
        print(f"rate={rate} requests={win.attempted} "
              f"completed_in_window={win.completed} failed={win.failed} "
              f"p50_ms={np.percentile(lat, 50):.1f} "
              f"p95_ms={np.percentile(lat, 95):.1f} "
              f"p95_first_quarter_ms={np.percentile(lat[:q], 95):.1f} "
              f"p95_last_quarter_ms={np.percentile(lat[-q:], 95):.1f} "
              f"drain_s={t_drained - win.t0 - win.seconds:.2f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
