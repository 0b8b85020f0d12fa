"""Plain reference for grid max-flow, and the check of a returned answer.

The reference builds the grid's arc list with NumPy (no per-cell loop) and
runs SciPy's Dinic on it in exact integer arithmetic. The check holds an
answer to what a user reads from it:

* ``flow_gap``: ``|flow - max-flow|``;
* ``cut_gap``: ``|capacity of the returned cut - max-flow|``. The cut is
  the boolean sink-side plane; its capacity is summed from the instance's
  own capacities, so only a minimum cut reads 0;
* ``unconverged``: answers whose ``converged`` flag is False.
"""
from __future__ import annotations

import numpy as np

UP, DOWN, LEFT, RIGHT = 0, 1, 2, 3
OFFSETS = ((-1, 0), (1, 0), (0, -1), (0, 1))      # [UP, DOWN, LEFT, RIGHT]

FIELDS = ("flow", "cut", "converged")
LIMITS = {"flow_gap": (0, "max"), "cut_gap": (0, "max"),
          "unconverged": (0, "sum")}


def _int(a) -> np.ndarray:
    a = np.asarray(a)
    out = np.rint(a).astype(np.int64)
    if not np.array_equal(out, a):
        raise ValueError("capacities must be whole numbers")
    return out


def _neighbour(a: np.ndarray, d: int, fill) -> np.ndarray:
    """``a`` at each cell's neighbour in direction ``d``; ``fill`` off-grid."""
    di, dj = OFFSETS[d]
    out = np.full_like(a, fill)
    H, W = a.shape
    out[max(0, -di):H - max(0, di), max(0, -dj):W - max(0, dj)] = \
        a[max(0, di):H - max(0, -di), max(0, dj):W - max(0, -dj)]
    return out


def arcs(instance) -> tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """``(tails, heads, capacities, n)``; source is ``n``, sink ``n + 1``."""
    cap, cs, ct = (_int(a) for a in instance)
    H, W = cs.shape
    n = H * W
    ids = np.arange(n).reshape(H, W)
    tails, heads, caps = [], [], []
    for d in range(4):
        nbr = _neighbour(ids, d, -1)
        keep = (cap[d] > 0) & (nbr >= 0)
        tails.append(ids[keep])
        heads.append(nbr[keep])
        caps.append(cap[d][keep])
    keep = cs > 0
    tails.append(np.full(int(keep.sum()), n))
    heads.append(ids[keep])
    caps.append(cs[keep])
    keep = ct > 0
    tails.append(ids[keep])
    heads.append(np.full(int(keep.sum()), n + 1))
    caps.append(ct[keep])
    return (np.concatenate(tails), np.concatenate(heads),
            np.concatenate(caps), n)


def solve(instance) -> int:
    """The exact max-flow value (SciPy's Dinic)."""
    import scipy.sparse as sp
    from scipy.sparse.csgraph import maximum_flow
    tails, heads, caps, n = arcs(instance)
    if caps.size and caps.max() >= 2 ** 31:
        raise ValueError("capacities exceed int32")
    graph = sp.csr_matrix((caps.astype(np.int32), (tails, heads)),
                          shape=(n + 2, n + 2))
    return int(maximum_flow(graph, n, n + 1, method="dinic").flow_value)


def cut_capacity(instance, sink_side) -> int:
    """Total capacity of the arcs from the source side to the sink side."""
    cap, cs, ct = (_int(a) for a in instance)
    t = np.asarray(sink_side, bool)
    if t.shape != cs.shape:
        raise ValueError(f"cut shape {t.shape}, instance {cs.shape}")
    s = ~t
    total = int(cs[t].sum()) + int(ct[s].sum())
    for d in range(4):
        total += int(cap[d][s & _neighbour(t, d, False)].sum())
    return total


def compare(instance, answer: dict, ref: int) -> dict:
    return {"flow_gap": abs(float(answer["flow"]) - ref),
            "cut_gap": abs(cut_capacity(instance, answer["cut"]) - ref),
            "unconverged": int(not bool(answer["converged"]))}
