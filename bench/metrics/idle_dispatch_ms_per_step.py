"""Device idle time per training step while the host enqueues the jitted
step (the Trainer's ``train.dispatch`` span), in ms (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.idle_ms_per_step(ctx, "dispatch")
