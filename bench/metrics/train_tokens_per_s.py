"""Tokens trained per second: every token of every step in the window over
the window's host-clock time (step boundaries taken by the step hook, each
after the Trainer's ``block_until_ready``)."""


def read(ctx):
    if not ctx["window_steps"]:
        return None
    return ctx["window_steps"] * ctx["tokens_per_step"] / ctx["window_s"]
