"""Device time per training step under the program's ``moe_dispatch``
scope (``models/moe.py``), in ms: the budget's top-k, the sort by expert,
the gather of the kept rows and the weighted scatter back, forward and
backward (``bench/stack_scopes.py``)."""
from bench import stack_scopes


def read(ctx):
    return stack_scopes.ms_per_step(ctx, "moe_dispatch")
