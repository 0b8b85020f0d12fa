"""Mean time of one solution-cache put (``ms``).

The scheduler's ``cache/put`` spans, one per resolved request, between its
``solve`` and its ``resolve`` (``serve/scheduler.py``): the payload checked
again, hashed, and stored with its solution.
"""


def read(record):
    t = [(s["t1"] - s["t0"]) * 1e3 for s in record["spans"] or ()
         if s["name"] == "cache/put"]
    return sum(t) / len(t) if t else None
