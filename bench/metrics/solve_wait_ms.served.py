"""Mean host wait for a dispatched batch's device solve (``ms``).

The ``solve/wait`` spans of ``core/batch.py``'s device stage: the read of
the batch's round counts, which blocks until the device is done.
"""


def read(record):
    t = [(s["t1"] - s["t0"]) * 1e3 for s in record["spans"] or ()
         if s["name"] == "solve/wait"]
    return sum(t) / len(t) if t else None
