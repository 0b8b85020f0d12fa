"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

* Busy time: the union of the intervals in which an operation ran on a
  device (its ``XLA Ops`` line), clipped to the measured window, which the
  harness marks with a host annotation named ``bench:window``.
* Kernel time: the summed device durations, and the count, of the ops
  whose HLO instruction name holds a kernel's ``MATCH``
  (``bench/kernels/``). A device event's name is the instruction's whole
  HLO text; only the part before `` = `` names the op itself, since the
  rest lists its operands.
* Top device operations by time, counting only ops that hold no other op
  (a ``while`` holds its body's ops), and the longest idle gaps, each
  named by the host activity that covers it (``label``): a host event of
  the trace, or a span of the program's tracer put on the trace's clock.

Every time here is in seconds; busy and kernel times are per chip, then
averaged (busy) or summed (kernels) over the cell's chips.
"""
from __future__ import annotations

import glob
import os
import re

import numpy as np

WINDOW = "bench:window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
TOP = 10


def find_xplane(trace_dir) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def union_length(intervals) -> float:
    """Total length covered by ``[(start, end), ...]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of ``[lo, hi]`` that no interval covers."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def label(gap, names, starts, ends) -> str:
    """The shortest host event that covers at least half of ``gap``; else
    the one that overlaps it most. ``names``, ``starts`` and ``ends`` are
    the host events as parallel sequences."""
    ov = np.minimum(ends, gap[1]) - np.maximum(starts, gap[0])
    cover = ov >= max((gap[1] - gap[0]) / 2, np.finfo(float).tiny)
    if cover.any():
        return names[int(np.argmin(np.where(cover, ends - starts, np.inf)))]
    if (ov > 0).any():
        return names[int(np.argmax(ov))]
    return "no host activity"


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = f32[8]{0} fusion(...)`` -> ``fusion.12``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def op_label(hlo_text: str, width: int = 96) -> str:
    """The op's name and the start of its signature, for the breakdown."""
    name, _, rest = hlo_text.partition(" = ")
    return f"{name.lstrip('%')} = {rest}"[:width]


def leaves(events) -> list:
    """The events that hold no other event (ops run one at a time, so an
    op that holds another holds the next one to start)."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    return [ev for ev, nxt in zip(evs, evs[1:] + [None])
            if nxt is None or nxt[1] >= ev[2] or nxt[2] > ev[2]]


def load(path):
    """``(devices, host)``: per device id its op events
    ``[(name, start_s, end_s)]``, and host events ``[(name, s, e)]``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices[int(m.group(1))] = [
                    (ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                    for ev in line.events]
            elif plane.name.startswith("/host:"):
                host.extend((ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                            for ev in line.events)
    return devices, host


def reduce(path, *, kernels: dict, n_chips: int, host_spans=None,
           window_t0: float | None = None) -> dict:
    """The numbers of one traced window (see the module docstring).

    ``host_spans`` are the program tracer's spans (``time.monotonic``
    seconds); ``window_t0`` is the monotonic time at which the window's
    annotation opened, which puts them on the trace's clock.
    """
    devices, host = load(path)
    marks = [(s, e) for n, s, e in host if n == WINDOW]
    if not marks:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    lo, hi = marks[0]
    ids = sorted(devices)[:n_chips]
    if not ids:
        raise ValueError(f"no device op line in {path}")
    host = [h for h in host if h[0] != WINDOW]
    if host_spans and window_t0 is not None:
        shift = lo - window_t0
        host += [(s["name"], s["t0"] + shift, s["t1"] + shift)
                 for s in host_spans]

    busy, by_op, kern, idle = [], {}, {}, []
    for i in ids:
        evs = [(n, max(s, lo), min(e, hi)) for n, s, e in devices[i]
               if e > lo and s < hi]
        iv = [(s, e) for _, s, e in evs]
        busy.append(union_length(iv))
        for n, s, e in leaves(evs):
            key = op_label(n)
            by_op[key] = by_op.get(key, 0.0) + (e - s) / len(ids)
            name = op_name(n)
            for k, match in kernels.items():
                if match in name:
                    t, c = kern.get(k, (0.0, 0))
                    kern[k] = (t + (e - s), c + 1)
        idle += gaps(iv, lo, hi)
    idle.sort(key=lambda g: g[0] - g[1])
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP]
    names = [n for n, _, _ in host]
    starts = np.array([s for _, s, _ in host], float)
    ends = np.array([e for _, _, e in host], float)
    return {"window_s": hi - lo,
            "busy_s": sum(busy) / len(busy),
            "busy_per_chip_s": busy,
            "kernels": {k: {"seconds": t, "calls": c}
                        for k, (t, c) in kern.items()},
            "top_ops": [[n, t] for n, t in top],
            "idle_gaps": [[label(g, names, starts, ends), g[1] - g[0]]
                          for g in idle[:TOP]]}
