"""Pallas TPU kernels for the compute hot spots (DESIGN.md §6).

Each kernel package ships:
  kernel.py — pl.pallas_call with explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (padding, layout, interpret switch)
  ref.py    — pure-jnp oracle used by the sweep tests

Off the TPU the wrappers run kernels with interpret=True; on the TPU they
compile through Mosaic, which holds each kernel to the compiler's scoped-VMEM
limit (16 MiB on v5e, of 128 MiB physical) and to (8, 128)-divisible block
shapes unless a block spans the whole array.  ``tests/test_tpu_compile.py``
compiles every kernel for a described v5e chip to keep both true.
"""
import jax
import numpy as np

# Block index maps and kernel bodies use int32 constants.  A bare ``0`` is an
# int64 literal under the engine's global x64 switch, and Mosaic refuses a
# kernel whose index map returns one or whose body converts one; a NumPy
# int32 scalar keeps its width.
I32_ZERO = np.int32(0)


def auto_interpret() -> bool:
    """Shared interpret=None resolution: compile on TPU, interpret elsewhere.

    Called at trace time (interpret is a static arg everywhere), so the
    backend probe never runs at import.
    """
    return jax.default_backend() != "tpu"
