"""``bidding``: row-wise top-2 of masked part-reduced costs (Pallas).

On the chip its HLO op is named ``vmap_jit_bidding__.<n>`` (a
``tpu_custom_call``, batched by ``vmap``), one call per auction round.
"""
MATCH = "bidding"
