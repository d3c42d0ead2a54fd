"""Device time under the program's ``attention`` scope
(``models/attention.py``) per training step, in ms: the self time of the
traced window's ops whose innermost layer scope is ``attention``, forward,
transpose and MALI's backward re-evaluations alike (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "attention")
