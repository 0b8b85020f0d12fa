"""Refine rounds per assignment answer (``rounds``), mean over the window.

The program's own per-instance ``rounds`` counter, summed over the
epsilon-scaling phases (``repro.core.assignment.cost_scaling``).
"""


def read(record):
    r = record["rounds"]
    return sum(r) / len(r) if r else None
