"""Push-relabel rounds per grid answer (``rounds``), mean over the window.

The program's own per-instance ``rounds`` counter
(``repro.core.maxflow.grid``, driven by ``repro.core.solver_loop``).
"""


def read(record):
    r = record["rounds"]
    return sum(r) / len(r) if r else None
