"""Device time per training step under the program's ``moe`` scope
(``models/moe.py``), in ms: the whole MoE layer, router, dispatch, held
experts and shared experts, forward, transpose and MALI's backward
re-evaluations alike (``bench/stack_scopes.py``)."""
from bench import stack_scopes


def read(ctx):
    return stack_scopes.ms_per_step(ctx, "moe")
