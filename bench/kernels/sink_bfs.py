"""``sink_bfs``: the ``pallas`` backend's global + gap relabel (Pallas).

On the chip its HLO op is named ``sink_bfs.<n>`` (a ``tpu_custom_call``),
one call per global relabel of the whole batch: the init BFS, each
cycle's relabel and the finalize BFS.
"""
MATCH = "sink_bfs"
