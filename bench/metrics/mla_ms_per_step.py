"""Device time per training step under the program's ``mla`` scope
(``models/mla.py``), in ms: every op whose name stack holds it, its
latent's norm included, forward, transpose and MALI's backward
re-evaluations alike (``bench/stack_scopes.py``)."""
from bench import stack_scopes


def read(ctx):
    return stack_scopes.ms_per_step(ctx, "mla")
