"""Device time under the program's ``optimizer`` scope
(``optim/optimizer.py``: clipping and the AdamW update) per training step,
in ms (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "optimizer")
