"""One ``pallas_call`` for every kernel: compiled on TPU, interpreted on CPU.

``pallas_call(kernel, grid=..., in_specs=..., ...)`` takes the arguments of
``jax.experimental.pallas.pallas_call`` except ``interpret``, which nobody
passes: both variants are traced and ``jax.lax.platform_dependent`` keeps
the one that matches the platform the program is lowered for. A program
lowered for the TPU (run there, or AOT-compiled here for a described chip)
holds only the Mosaic kernel (``tpu_custom_call`` in its HLO); a program
lowered for the CPU holds only the interpreter's plain HLO. No caller can
leave a TPU program in interpret mode by forgetting an argument.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, **spec):
    """``pl.pallas_call(kernel, **spec)`` with ``interpret`` chosen per
    lowering platform (CPU: interpret; TPU: compiled)."""
    interpreted = pl.pallas_call(  # odelint: disable=R003 -- grid in **spec
        kernel, interpret=True, **spec)
    compiled = pl.pallas_call(  # odelint: disable=R003 -- grid in **spec
        kernel, interpret=False, **spec)

    def run(*args):
        return jax.lax.platform_dependent(*args, cpu=interpreted,
                                          tpu=compiled)

    return run
