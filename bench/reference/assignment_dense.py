"""Plain reference for dense assignment, and the check of a returned answer.

The reference is SciPy's ``linear_sum_assignment`` (a Hungarian-type
shortest augmenting path method) maximising total weight. The check holds
an answer to what a user reads from it:

* ``not_permutation``: answers whose ``col_of_row`` is not a permutation;
* ``weight_gap``: optimum minus the weight of the returned permutation,
  summed from the instance's own costs (the optimum when it reads 0);
* ``reported_gap``: ``|reported weight - weight of the permutation|``;
* ``unconverged``: answers whose ``converged`` flag is False.
"""
from __future__ import annotations

import numpy as np

FIELDS = ("col_of_row", "weight", "converged")
LIMITS = {"weight_gap": (0, "max"), "reported_gap": (0, "max"),
          "not_permutation": (0, "sum"), "unconverged": (0, "sum")}


def solve(instance) -> int:
    """The optimal (maximum) total weight."""
    from scipy.optimize import linear_sum_assignment
    w = np.asarray(instance, np.int64)
    rows, cols = linear_sum_assignment(w, maximize=True)
    return int(w[rows, cols].sum())


def compare(instance, answer: dict, ref: int) -> dict:
    w = np.asarray(instance, np.int64)
    n = w.shape[0]
    col = np.asarray(answer["col_of_row"]).astype(np.int64)
    perm = col.shape == (n,) and np.array_equal(np.sort(col), np.arange(n))
    got = int(w[np.arange(n), col].sum()) if perm else None
    return {"not_permutation": int(not perm),
            # a non-permutation has no weight: count the whole optimum
            "weight_gap": ref - got if perm else abs(ref) + 1,
            "reported_gap": abs(int(answer["weight"]) - got) if perm else 0,
            "unconverged": int(not bool(answer["converged"]))}
