"""Device idle share of the traced window, batch cells (``%``).

1 minus the union of device-op intervals over the window, averaged over
the cell's chips (``bench/trace.py``).
"""


def read(record):
    dev = record["device"]
    if not dev or dev["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
