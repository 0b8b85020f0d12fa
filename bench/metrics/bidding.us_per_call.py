"""Device time of one ``bidding`` call (``us``), mean over the window.

The summed device durations of the kernel's ops over their count, from
the traced window (``bench/trace.py``); one call covers every row of the
batch for one auction round.
"""


def read(record):
    k = (record["device"] or {}).get("kernels", {}).get("bidding")
    if not k or k["calls"] == 0:
        return None
    return 1e6 * k["seconds"] / k["calls"]
