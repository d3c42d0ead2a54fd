"""Device time under the program's ``head_loss`` scope (``models/lm.py``:
the output head and the chunked cross-entropy) per training step, in ms
(``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "head_loss")
