"""Device time of MALI's backward sweep (the program's ``mali_backward``
scope, ``core/mali.py``) per training step, in ms: every op under it, the
f-evals it re-runs included, so it overlaps the layer scopes
(``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "mali_backward")
