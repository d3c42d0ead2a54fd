"""Device time of the ALF Mosaic kernels per training step, in ms: the
summed durations of their calls in the traced window (averaged over
chips), over the window's steps. An ALF kernel call is a Mosaic call
whose arrays are all the flattened ODE state (``bench/flops.py``)."""
from bench import flops


def read(ctx):
    if not ctx["job"].ode or not ctx["window_steps"]:
        return None
    calls = flops.alf_calls(ctx)
    if not calls:
        return None
    return 1e3 * sum(s for _, _, s in calls) / ctx["window_steps"]
