"""95th percentile of the scheduler's ``queue-wait`` spans (``ms``).

From submit to the flush that took the request (``serve/scheduler.py``).
"""
import numpy as np


def read(record):
    w = [(s["t1"] - s["t0"]) * 1e3 for s in record["spans"] or ()
         if s["name"] == "queue-wait"]
    return float(np.percentile(w, 95)) if w else None
