"""99th percentile of the ``validate`` spans (``ms``).

The payload check ``AsyncSolverEngine.submit`` runs on the caller's thread
before a request is queued (``serve/scheduler.py``), inside its ``submit``
span.
"""
import numpy as np


def read(record):
    t = [(s["t1"] - s["t0"]) * 1e3 for s in record["spans"] or ()
         if s["name"] == "validate"]
    return float(np.percentile(t, 99)) if t else None
