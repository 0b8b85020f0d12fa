"""Device time of one ``sink_bfs`` call (``us``), mean over the window.

The summed device durations of the kernel's ops over their count, from
the traced window (``bench/trace.py``); one call is one whole global
relabel of the batch, every grid swept to its own fixpoint. A program
without the kernel, or a grid shape that relabels in XLA, reads nothing.
"""


def read(record):
    k = (record["device"] or {}).get("kernels", {}).get("sink_bfs")
    if not k or k["calls"] == 0:
        return None
    return 1e6 * k["seconds"] / k["calls"]
