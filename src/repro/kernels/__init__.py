"""Pallas TPU kernels for MARS's compute hot-spots.

Each kernel package has:
    <name>.py  — pl.pallas_call + explicit BlockSpec VMEM tiling
    ops.py     — jit'd public wrapper (padding, dtype plumbing, vmap rules)
    ref.py     — pure-jnp oracle the kernel is tested against

Kernels compile with Mosaic on a TPU.  On the CPU backend (the test suite)
they run in Pallas interpret mode: ``INTERPRET`` is set from the backend at
import, and a test that compiles for a described TPU flips it itself.
"""
import jax

INTERPRET = jax.default_backend() == "cpu"
