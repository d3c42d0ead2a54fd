"""One ``pallas_call`` for every kernel: compiled on TPU, interpreted on CPU.

``pallas_call(kernel, grid=..., in_specs=..., ...)`` takes the arguments of
``jax.experimental.pallas.pallas_call`` except ``interpret``, which nobody
passes: both variants are traced and ``jax.lax.platform_dependent`` keeps
the one that matches the platform the program is lowered for. A program
lowered for the TPU (run there, or AOT-compiled here for a described chip)
holds only the Mosaic kernel (``tpu_custom_call`` in its HLO); a program
lowered for the CPU holds only the interpreter's plain HLO. No caller can
leave a TPU program in interpret mode by forgetting an argument.

Every kernel is named (``name=``, required): the name is the Mosaic
kernel's ``kernel_name``, so a kernel keeps it in the compiled program and
in a profile, whatever the surrounding code is called.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def pallas_call(kernel, *, name: str, **spec):
    """``pl.pallas_call(kernel, name=name, **spec)`` with ``interpret``
    chosen per lowering platform (CPU: interpret; TPU: compiled)."""
    interpreted = pl.pallas_call(  # odelint: disable=R003 -- grid in **spec
        kernel, interpret=True, name=name, **spec)
    compiled = pl.pallas_call(  # odelint: disable=R003 -- grid in **spec
        kernel, interpret=False, name=name, **spec)

    def run(*args):
        return jax.lax.platform_dependent(*args, cpu=interpreted,
                                          tpu=compiled)

    return run
