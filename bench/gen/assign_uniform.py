"""Dense square assignment instances with uniform integer costs.

Every entry is uniform on ``[0, max_cost]`` (complete bipartite graph),
as in the DIMACS assignment generator's uniform family. Instances are
``(n, n)`` int32 weight matrices; the solver maximises total weight.
"""
from __future__ import annotations

import numpy as np


def pool(rng: np.random.Generator, params: dict) -> list[np.ndarray]:
    """``params["pool"]`` distinct ``(n, n)`` matrices."""
    n, top = params["n"], params["max_cost"]
    return [rng.integers(0, top + 1, size=(n, n), dtype=np.int32)
            for _ in range(params["pool"])]
