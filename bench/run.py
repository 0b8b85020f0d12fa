#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout that holds ``BENCHMARK.json``. The run sets
up (generates the cell's instances from the seed, warms every program the
window uses), measures for ``--seconds``, checks the answers of a seeded
sample of instances against the plain reference, and prints one JSON
object as the last line of standard output: end-to-end metrics with
``--trace 0``, per-layer metrics and a device-trace breakdown with
``--trace 1``. ``--control`` runs the cell with the configuration's
control settings (an early-stopped solve), which must read not correct.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 2. JAX's compilation cache is
``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache`` in the checkout.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

# the TPU runtime would otherwise write its logs under /tmp, outside the
# run's own directories
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = pathlib.Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="solve with the configuration's control settings")
    ap.add_argument("--dump", help="write each request's timing to this file")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    cell = harness.load_cell(ROOT, args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"has {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    cache = harness.use_compile_cache(ROOT / ".jax_cache")
    print(f"[setup] workload={args.workload} seed={args.seed} "
          f"device={devices[0].device_kind} count={len(devices)} "
          f"jax={jax.__version__} compile_cache={cache}", flush=True)
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), T_START,
                              control=args.control, dump=args.dump)
    harness.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
