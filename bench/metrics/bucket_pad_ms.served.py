"""Mean host pad-and-bucket time per flushed batch (``ms``).

The engine's ``bucket/pad`` spans around ``core/batch.py``'s
``prepare_buckets``.
"""


def read(record):
    t = [(s["t1"] - s["t0"]) * 1e3 for s in record["spans"] or ()
         if s["name"] == "bucket/pad"]
    return sum(t) / len(t) if t else None
