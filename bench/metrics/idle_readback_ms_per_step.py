"""Device idle time per training step while the host reads the step's
metrics back and records them (the Trainer's ``train.readback`` and
``train.record`` spans), in ms (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.idle_ms_per_step(ctx, "readback", "record")
