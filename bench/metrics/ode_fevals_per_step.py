"""Dynamics evaluations per training step, from the step's own counter
(``RunStats.n_fevals``, summed over every ODE branch's solve): forward
f-evals of all branches, ``(n_steps + 1)`` per branch for fixed-step ALF;
MALI's backward re-evaluations are not counted in it. Mean over the
traced window's steps."""


def read(ctx):
    if not ctx["job"].ode or not ctx["fevals"]:
        return None
    return sum(ctx["fevals"]) / len(ctx["fevals"])
