"""Mean requests per dispatched batch (``inst``).

The ``n_real`` attribute of the engine's ``device-solve`` spans
(``serve/engine.py``).
"""


def read(record):
    n = [s["attrs"]["n_real"] for s in record["spans"] or ()
         if s["name"] == "device-solve" and "n_real" in s["attrs"]]
    return sum(n) / len(n) if n else None
