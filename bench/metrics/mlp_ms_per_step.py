"""Device time under the program's ``mlp`` scope (``models/mlp.py``) per
training step, in ms: the self time of the traced window's ops whose
innermost layer scope is ``mlp`` (``bench/scopes.py``)."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms_per_step(ctx, "mlp")
