"""Where a reference keeps a model that one chip cannot hold.

Over ``c`` chips, layer ``i`` of ``n`` lives on chip ``i * c // n``: its
weights, its gradient accumulator, its gradient history and its input
activation. The embedding lives on the first chip, every other leaf
outside the layers (the final norm, the head) on the last. The
arithmetic stays the same and in the same order; only placement moves.
With one chip, or none given, nothing is placed: the same ops run on the
same device as an unplaced reference.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax


def layer_chip(i: int, n_layers: int, chips: int) -> int:
    """The chip that holds layer ``i`` of ``n_layers`` over ``chips``."""
    return i * chips // n_layers


class Placement:
    """The devices of a reference's parts; ``None`` where nothing moves."""

    def __init__(self, devices: Optional[Sequence], n_layers: int):
        self.devices = list(devices) if devices and len(devices) > 1 else []
        self.n_layers = n_layers

    def layer(self, i: int):
        if not self.devices:
            return None
        return self.devices[layer_chip(i, self.n_layers, len(self.devices))]

    @property
    def first(self):
        return self.devices[0] if self.devices else None

    @property
    def last(self):
        return self.devices[-1] if self.devices else None


def put(x, device):
    """``x`` on ``device``; ``x`` itself where ``device`` is None."""
    return x if device is None else jax.device_put(x, device)
