"""Device idle share of the traced window, served cells (``%``).

Same reduction as ``device_idle.batch``; split so that each moves the
end-to-end metric of its own cells.
"""


def read(record):
    dev = record["device"]
    if not dev or dev["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
