"""``grid_push``: the per-node decision of one push-relabel round (Pallas).

On the chip its HLO op is named ``grid_push_decide.<n>`` (a
``tpu_custom_call``), one call per Jacobi round over the whole batch.
"""
MATCH = "grid_push"
