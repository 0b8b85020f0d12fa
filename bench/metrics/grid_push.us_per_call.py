"""Device time of one ``grid_push`` call (``us``), mean over the window.

The summed device durations of the kernel's ops over their count, from
the traced window (``bench/trace.py``); one call covers the whole batch's
planes for one round.
"""


def read(record):
    k = (record["device"] or {}).get("kernels", {}).get("grid_push")
    if not k or k["calls"] == 0:
        return None
    return 1e6 * k["seconds"] / k["calls"]
