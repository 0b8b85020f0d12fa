"""4-connected grid-cut instances with uniform integer capacities.

Neighbour capacities and terminal capacities are uniform on
``[0, max_cap]``; each terminal arc is kept with probability
``terminal_density``. Arcs that would leave the grid have capacity 0.
Instances are ``(cap_nbr (4, H, W), cap_src (H, W), cap_sink (H, W))``
float32 arrays, directions ``[UP, DOWN, LEFT, RIGHT]``.
"""
from __future__ import annotations

import numpy as np

UP, DOWN, LEFT, RIGHT = 0, 1, 2, 3


def instance(rng: np.random.Generator, height: int, width: int,
             max_cap: int, terminal_density: float) -> tuple:
    cap = rng.integers(0, max_cap + 1, size=(4, height, width)).astype(
        np.float32)
    cap[UP, 0, :] = 0
    cap[DOWN, -1, :] = 0
    cap[LEFT, :, 0] = 0
    cap[RIGHT, :, -1] = 0
    cs = rng.integers(0, max_cap + 1, size=(height, width)).astype(np.float32)
    ct = rng.integers(0, max_cap + 1, size=(height, width)).astype(np.float32)
    cs *= rng.random((height, width)) < terminal_density
    ct *= rng.random((height, width)) < terminal_density
    return cap, cs, ct


def pool(rng: np.random.Generator, params: dict) -> list[tuple]:
    """``params["pool"]`` distinct instances of the config's sizes."""
    return [instance(rng, params["height"], params["width"],
                     params["max_cap"], params["terminal_density"])
            for _ in range(params["pool"])]
