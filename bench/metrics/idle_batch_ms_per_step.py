"""Device idle time per training step while the host builds and places the
next batch (the Trainer's ``train.batch`` span), in ms
(``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.idle_ms_per_step(ctx, "batch")
