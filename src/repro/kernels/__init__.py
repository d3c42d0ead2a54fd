"""Pallas TPU kernels + jnp oracles. Kernels compile (Mosaic) on TPU and run
in interpret mode only on CPU (``repro.kernels.dispatch``)."""
from .registry import NO_REVERSE_RULE, forward_only_ops, no_reverse_reason

__all__ = ["NO_REVERSE_RULE", "no_reverse_reason", "forward_only_ops"]
