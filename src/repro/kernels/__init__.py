"""Pallas TPU kernels for the hot loops the paper hand-optimizes.

Each kernel package holds ``kernel.py`` (the ``pallas_call``), ``ops.py``
(the jitted wrappers the solvers call) and ``ref.py`` (a pure-jnp
oracle). The kernel entry points compile through Mosaic by default; the
ops wrappers ask ``interpret_mode`` whether to interpret instead.
"""
import jax


def interpret_mode() -> bool:
    """Whether the ops wrappers run their kernels in Pallas interpret mode.

    True exactly when JAX's default backend is not a TPU, so tests and
    examples on a CPU host interpret, and on a TPU every kernel compiles.
    """
    return jax.default_backend() != "tpu"
