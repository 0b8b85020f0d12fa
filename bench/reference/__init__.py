"""Plain references and the comparisons that decide ``correct``.

One module per reference name in a config. Each module imports NumPy and
SciPy only, never the program, and exposes:

* ``FIELDS``: the result fields a run keeps for the comparison;
* ``solve(instance)``: the reference answer (runs in a worker process);
* ``compare(instance, answer, ref)``: ``{number: value}`` for one answer;
* ``LIMITS``: ``{number: (limit, how answers combine: "max" or "sum")}``.
"""
