"""Device time per training step under none of the program's layer scopes,
in ms: what the scope metrics leave unnamed (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, None)
