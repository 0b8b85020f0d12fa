"""The Pallas kernels the per-layer metrics time, one module per kernel.

Each module exposes ``MATCH``: a substring of the kernel's HLO instruction
name in a profiler trace, as read off a trace recorded on the chip.
"""
